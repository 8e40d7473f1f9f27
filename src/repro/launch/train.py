"""Production training driver.

Builds the mesh, shards the train state, runs the data pipeline, training
loop, periodic async checkpointing, and the fault-tolerance hooks (heartbeat,
straggler policy, recovery supervision).  On this CPU container it runs real
steps with a local mesh at smoke scale; on a TPU fleet the same driver binds
``make_production_mesh``.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
        --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from .mesh import make_local_mesh, make_production_mesh
from ..checkpoint.checkpointer import Checkpointer
from ..config import use_compile_cache
from ..configs import get_config
from ..configs.base import TrainConfig
from ..data.pipeline import make_batch_iterator
from ..models import build_model
from ..runtime.fault_tolerance import StragglerPolicy
from ..sharding import batch_sharding, params_sharding
from ..train import init_train_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    tcfg = TrainConfig(global_batch=args.batch, seq_len=args.seq, lr=args.lr,
                       warmup_steps=max(1, args.steps // 10),
                       total_steps=args.steps,
                       microbatches=args.microbatches,
                       grad_compression=args.grad_compression)
    model = build_model(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_local_mesh())

    with jax.set_mesh(mesh):
        state = init_train_state(model, jax.random.PRNGKey(tcfg.seed), tcfg)
        p_shard = params_sharding(state.params, mesh, cfg)
        state = state._replace(
            params=jax.tree.map(jax.device_put, state.params, p_shard))
        step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0,))

        ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
        start = 0
        if ckpt and args.resume and ckpt.latest_step() is not None:
            restored, start = ckpt.restore(
                jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                             state.params))
            state = state._replace(params=restored)
            print(f"[train] resumed from step {start}")

        it = make_batch_iterator(cfg, tcfg, start_step=start)
        straggler = StragglerPolicy()
        t_start = time.time()
        for step in range(start, args.steps):
            batch = next(it)
            batch = {k: jax.device_put(jnp.asarray(v), s)
                     for (k, v), s in zip(
                         batch.items(),
                         batch_sharding(batch, mesh).values())}
            t0 = time.time()
            state, metrics = step_fn(state, batch)
            dt = time.time() - t0
            verdict = straggler.observe(dt)
            if verdict != "ok":
                print(f"[straggler] step {step}: {dt:.2f}s -> {verdict}")
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"[train] step {step} loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e} {dt*1e3:.0f}ms")
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, state.params)
        if ckpt:
            ckpt.save(args.steps, state.params, blocking=True)
        it.close()
        tok_s = (args.steps - start) * tcfg.global_batch * tcfg.seq_len \
            / (time.time() - t_start)
        print(f"[train] done: {tok_s:.0f} tokens/s "
              f"(straggler skips: {straggler.skipped})")
    return state


if __name__ == "__main__":
    main()
