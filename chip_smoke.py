#!/usr/bin/env python3
"""Smoke run of the main path on one TPU chip, in one process.

    python chip_smoke.py                # one chip: kernel + serve phases
    python chip_smoke.py --four-chips   # ShardedPlan across four chips only

Phases (one chip):

1. **kernel** — one qwen2-1.5b FFN layer (d_model 1536, d_ff 8960) pruned
   to 75% block sparsity in 128x128 blocks, planned with
   ``compress_ffn(backend="pallas")`` and run through the jitted
   ``sparse_ffn_apply`` at a decode (4 tokens) and a prefill (512 tokens)
   shape; ``x @ W_gate`` pinned to each of the six dataflows; one plan at
   25% sparsity that takes the dense escape hatch.  Every result is
   compared with a float32 ``precision=HIGHEST`` product of the masked
   dense weights, and every sparse case's lowered HLO must hold a
   compiled Pallas kernel (``tpu_custom_call``), so nothing ran
   interpreted.
2. **serve** — ``repro.launch.serve.main`` with qwen2-1.5b at full width
   (random weights from a seed): every request must return its tokens,
   inside the vocabulary, and one prompt's prefill logits must agree with
   ``model.logits`` on the same prompt.

``--four-chips`` runs only the ``ShardedPlan`` path across a 4-device
mesh (an ``op_m`` k-split merged with ``psum`` and a ``gust_m`` m-split)
and compares each with the same plan on one chip.

There is no CPU fallback: without a TPU the script exits non-zero before
any phase.  Times printed are single cold runs, not benchmark figures.  The
last line of standard output is one JSON object
``{"ok": true, "device": {...}}``, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-1.5b"
SPARSITY = 0.75          # kernel path (work ratio 0.25 < DENSE_THRESHOLD)
DENSE_SPARSITY = 0.25    # dense escape hatch (work ratio 0.75)
DECODE_TOKENS, PREFILL_TOKENS = 4, 512
KERNEL_TOL = 2e-2        # normalized max error vs the float32 reference
LOGITS_TOL = 3e-2        # bf16 forward vs prefill (tests/test_models_decode)
SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def norm_err(out, ref) -> float:
    import numpy as np

    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    if out.shape != ref.shape:
        raise AssertionError(f"shape {out.shape} != reference {ref.shape}")
    if not np.isfinite(out).all():
        raise AssertionError("non-finite values in the result")
    return float(np.abs(out - ref).max() / (np.abs(ref).max() + 1e-12))


def assert_kernel(hlo_text: str, case: str) -> None:
    """A compiled Pallas TPU kernel is in the program (not interpreted)."""
    if "tpu_custom_call" not in hlo_text:
        raise AssertionError(f"{case}: no tpu_custom_call in the lowered "
                             "program — the kernel did not compile for TPU")


def run_case(case: str, fn, x, ref, *, kernel: bool, checks: list) -> None:
    """Lower, check the HLO, compile, run once, compare with ``ref``."""
    import jax

    t0 = time.perf_counter()
    lowered = jax.jit(fn).lower(x)
    if kernel:
        assert_kernel(lowered.as_text(), case)
    compiled = lowered.compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(x))
    t2 = time.perf_counter()
    err = norm_err(out, ref)
    ok = err <= KERNEL_TOL
    checks.append((case, ok))
    log(f"[kernel] {case}: norm_max_err={err!r} "
        f"{'ok' if ok else f'FAIL (> {KERNEL_TOL})'} "
        f"lower+compile_s={t1 - t0!r} first_run_s={t2 - t1!r}")


def ffn_weights(sparsity: float):
    """One qwen2-1.5b FFN layer pruned to ``sparsity`` (models.ffn) and its
    masked dense float32 weights (the reference operands)."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models.ffn import _masked_weight, ffn_init

    cfg = dataclasses.replace(get_config(ARCH), ffn_block_sparsity=sparsity)
    p = ffn_init(jax.random.PRNGKey(SEED), cfg)
    mask = p["block_mask"]
    dense = tuple(np.asarray(_masked_weight(p[k]["w"], m), np.float32)
                  for k, m in (("w_gate", mask), ("w_up", mask),
                               ("w_down", mask.T)))
    log(f"[kernel] {ARCH} FFN d_model={cfg.d_model} d_ff={cfg.d_ff} "
        f"block_sparsity={sparsity} kept_blocks={int(mask.sum())}/{mask.size}")
    return cfg, p, dense


def kernel_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import flexagon_plan
    from repro.core.dataflows import DATAFLOWS
    from repro.models.sparse_linear import compress_ffn, sparse_ffn_apply

    hi = jax.lax.Precision.HIGHEST
    cfg, p, (wg, wu, wd) = ffn_weights(SPARSITY)
    checks: list = []

    def ref_ffn(x):
        x2d = x.reshape(-1, cfg.d_model).astype(jnp.float32)
        g = jax.nn.silu(jnp.dot(x2d, wg, precision=hi))
        u = jnp.dot(x2d, wu, precision=hi)
        return jnp.dot(g * u, wd, precision=hi).reshape(x.shape)

    comp = compress_ffn(p, tokens=DECODE_TOKENS, backend="pallas", block=128)
    rng = np.random.default_rng(SEED)
    for name, tokens in (("decode", DECODE_TOKENS),
                         ("prefill", PREFILL_TOKENS)):
        x = jnp.asarray(rng.standard_normal((1, tokens, cfg.d_model)),
                        jnp.float32)
        entry = comp.specialize(tokens)
        flows = (entry.plan_in.dataflow, entry.plan_out.dataflow)
        sparse = all("dense" not in e.aux
                     for e in (entry.plan_in, entry.plan_out))
        if not sparse:
            raise AssertionError(f"sparse_ffn_apply {name}: a plan took the "
                                 "dense escape hatch at 75% sparsity")
        run_case(f"sparse_ffn_apply {name} tokens={tokens} "
                 f"dataflows(in,out)={flows}",
                 lambda x: sparse_ffn_apply(comp, x), x, ref_ffn(x),
                 kernel=True, checks=checks)

    for tokens in (DECODE_TOKENS, PREFILL_TOKENS):
        x = jnp.asarray(rng.standard_normal((tokens, cfg.d_model)),
                        jnp.float32)
        ref = jnp.dot(x, wg, precision=hi)
        for dataflow in DATAFLOWS:
            plan = flexagon_plan((tokens, cfg.d_model), wg, dataflow=dataflow,
                                 block_shape=(128, 128, 128),
                                 backend="pallas")
            if "dense" in plan.aux:
                raise AssertionError(f"{dataflow}: dense escape at 75%")
            w = plan.pack_b(wg)
            run_case(f"x@W_gate {dataflow} tokens={tokens}",
                     lambda x, plan=plan, w=w: plan.apply(x, w), x, ref,
                     kernel=True, checks=checks)

    _, _, (wg25, _, _) = ffn_weights(DENSE_SPARSITY)
    x = jnp.asarray(rng.standard_normal((PREFILL_TOKENS, cfg.d_model)),
                    jnp.float32)
    plan = flexagon_plan((PREFILL_TOKENS, cfg.d_model), wg25,
                         block_shape=(128, 128, 128), backend="pallas")
    if "dense" not in plan.aux:
        raise AssertionError("25% sparsity did not take the dense escape")
    w = plan.pack_b(wg25)
    run_case(f"x@W_gate dense-escape {plan.dataflow} sparsity="
             f"{DENSE_SPARSITY} tokens={PREFILL_TOKENS}",
             lambda x: plan.apply(x, w), x, jnp.dot(x, wg25, precision=hi),
             kernel=False, checks=checks)

    failed = [c for c, ok in checks if not ok]
    if failed:
        raise AssertionError(f"{len(failed)} kernel case(s) out of "
                             f"tolerance: {failed}")


def serve_phase() -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve

    requests, slots, max_new = 4, 4, 8
    t0 = time.perf_counter()
    engine, reqs = serve.main(["--arch", ARCH, "--requests", str(requests),
                               "--slots", str(slots),
                               "--max-new", str(max_new),
                               "--seed", str(SEED)])
    log(f"[serve] serve.main wall_s={time.perf_counter() - t0!r} "
        "(cold: init + compiles; not a benchmark figure)")
    cfg = engine.model.cfg
    if len(reqs) != requests:
        raise AssertionError(f"{len(reqs)} requests, expected {requests}")
    for r in reqs:
        toks = np.asarray(r.out_tokens)
        if toks.size != max_new:
            raise AssertionError(f"request {r.rid}: {toks.size} tokens, "
                                 f"expected {max_new}")
        if ((toks < 0) | (toks >= cfg.vocab)).any():
            raise AssertionError(f"request {r.rid}: token outside the "
                                 f"vocabulary {cfg.vocab}: {toks}")
    log(f"[serve] {requests} requests x {max_new} tokens, all inside "
        f"vocab={cfg.vocab}")

    model, params = engine.model, engine.params
    prompt = jnp.asarray(reqs[0].prompt, jnp.int32)[None]
    logits_pf, _ = model.prefill(params, prompt,
                                 model.init_cache(1, engine.max_seq,
                                                  engine.dtype))
    full = model.logits(params, prompt, remat=False)
    ref = np.asarray(full[0, -1], np.float32)
    err = norm_err(logits_pf[0, -1], ref)
    first = int(np.argmax(np.asarray(logits_pf[0, -1], np.float32)))
    log(f"[serve] prefill vs model.logits, prompt_len={prompt.shape[1]}: "
        f"norm_max_err={err!r} (tol {LOGITS_TOL}); engine first token "
        f"{reqs[0].out_tokens[0]}, prefill argmax {first}, "
        f"forward argmax {int(np.argmax(ref))}")
    if err > LOGITS_TOL:
        raise AssertionError(f"prefill logits disagree with model.logits: "
                             f"{err!r} > {LOGITS_TOL}")


def four_chip_phase(devices) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro import flexagon_plan
    from repro.dist import ShardedPlan

    if len(devices) < 4:
        raise AssertionError(f"--four-chips needs 4 devices, "
                             f"found {len(devices)}")
    mesh = Mesh(np.asarray(devices[:4]), ("shards",))
    cfg, _, (wg, _, _) = ffn_weights(SPARSITY)
    hi = jax.lax.Precision.HIGHEST
    rng = np.random.default_rng(SEED)
    x_host = rng.standard_normal((PREFILL_TOKENS, cfg.d_model)).astype(
        np.float32)
    replicated = NamedSharding(mesh, P())
    x_mesh = jax.device_put(x_host, replicated)
    w_mesh = jax.device_put(wg, replicated)
    x_one = jax.device_put(x_host, devices[0])
    ref = np.asarray(jnp.dot(x_one, jax.device_put(wg, devices[0]),
                             precision=hi))
    failed = []
    for dataflow in ("op_m", "gust_m"):
        one = flexagon_plan((PREFILL_TOKENS, cfg.d_model), wg,
                            dataflow=dataflow, block_shape=(128, 128, 128),
                            backend="pallas")
        out_one = jax.block_until_ready(
            jax.jit(lambda x, w: one.apply(x, w))(x_one, one.pack_b(wg)))

        plan = flexagon_plan((PREFILL_TOKENS, cfg.d_model), wg,
                             dataflow=dataflow, block_shape=(128, 128, 128),
                             backend="pallas", mesh=mesh)
        if not isinstance(plan, ShardedPlan) or not plan.runs_sharded:
            raise AssertionError(f"{dataflow}: the plan would not run the "
                                 "shard_map path across the mesh")
        fn = jax.jit(lambda x, w: plan.apply(x, w))
        t0 = time.perf_counter()
        lowered = fn.lower(x_mesh, w_mesh)
        assert_kernel(lowered.as_text(), f"sharded {dataflow}")
        compiled = lowered.compile()
        hlo = compiled.as_text()
        t1 = time.perf_counter()
        out = jax.block_until_ready(compiled(x_mesh, w_mesh))
        t2 = time.perf_counter()
        n_dev = len(out.sharding.device_set)
        has_all_reduce = "all-reduce" in hlo
        err_one = norm_err(out, out_one)
        err_ref = norm_err(out, ref)
        ok = (n_dev == 4 and err_one <= KERNEL_TOL and err_ref <= KERNEL_TOL
              and (has_all_reduce or plan.collective != "psum"))
        log(f"[four-chips] {dataflow} axis={plan.axis} shards={plan.n_shards}"
            f" collective={plan.collective} all-reduce_in_hlo="
            f"{has_all_reduce} output_devices={n_dev} "
            f"err_vs_one_chip={err_one!r} err_vs_f32_ref={err_ref!r} "
            f"{'ok' if ok else 'FAIL'} lower+compile_s={t1 - t0!r} "
            f"first_run_s={t2 - t1!r}")
        if not ok:
            failed.append(dataflow)
    if failed:
        raise AssertionError(f"sharded plan check failed for {failed}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the ShardedPlan phase on a 4-chip mesh")
    args = ap.parse_args(argv)

    from repro.config import use_compile_cache

    cache_dir = use_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] FAIL: no TPU — JAX's default device is "
              f"{dev.platform!r}; this script has no CPU fallback",
              file=sys.stderr)
        return 1
    log(f"[chip_smoke] platform={dev.platform} device_kind={dev.device_kind}"
        f" count={len(devices)} jax={jax.__version__} "
        f"compile_cache={cache_dir}")

    phases = ([("four-chips", lambda: four_chip_phase(devices))]
              if args.four_chips else
              [("kernel", kernel_phase), ("serve", serve_phase)])
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:       # noqa: BLE001 — report, run the rest, fail
            traceback.print_exc()
            failed.append(name)
        log(f"[chip_smoke] phase {name}: "
            f"{'FAILED' if name in failed else 'passed'} "
            f"wall_s={time.perf_counter() - t0!r} (cold, includes compiles)")
    if failed:
        print(f"[chip_smoke] FAIL: phase(s) {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
