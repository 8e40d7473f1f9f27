"""System under test: one block-pruned SwiGLU FFN layer, planned once by
``repro.models.sparse_linear.compress_ffn`` and run through the jitted
``sparse_ffn_apply`` on the Pallas backend.

``sparse_ffn_apply(comp, x)`` looks its plans and packed weights up in a
``CompressedFFN``.  Closed over by ``jax.jit``, those would be baked into the
program as constants: hundreds of megabytes of weights, and index arrays
whose values change with the seed, so every seed would compile anew.  The
step therefore hands ``sparse_ffn_apply`` the same planned entry with its
seed-dependent arrays (the packed weight blocks and the kernels' work
lists) as the jitted program's arguments, as a server holds its weights.
The plans, the packing, the kernels and the arithmetic are the program's.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import FlexagonPlan
from repro.models.sparse_linear import compress_ffn, sparse_ffn_apply

from .. import inputs, reference
from ..work import swiglu_ffn_work

SCHEDULE = "stream_schedule"


class _Planned:
    """Stands in for the ``CompressedFFN`` inside the jitted step: its
    ``specialize`` hands ``sparse_ffn_apply`` one planned entry."""

    def __init__(self, entry, tokens: int):
        self._entry, self._tokens = entry, tokens

    def specialize(self, tokens: int):
        if tokens != self._tokens:
            raise ValueError(f"planned for {self._tokens} tokens, "
                             f"called with {tokens}")
        return self._entry


def _dynamic(entry) -> dict:
    for plan in (entry.plan_in, entry.plan_out):
        if not isinstance(plan, FlexagonPlan) or SCHEDULE not in plan.aux:
            raise NotImplementedError(
                f"{type(plan).__name__} without a stream schedule: only "
                "single-device pallas plans are driven here")
    return {"w": (entry.w_gate.data, entry.w_up.data, entry.w_down.data),
            "s": (entry.plan_in.aux[SCHEDULE], entry.plan_out.aux[SCHEDULE])}


def _rebuild(entry, dyn: dict):
    def plan(p, sched):
        return dataclasses.replace(p, aux={**p.aux, SCHEDULE: sched})

    def weight(op, data):
        return dataclasses.replace(op, data=data)

    wg, wu, wd = dyn["w"]
    s_in, s_out = dyn["s"]
    return dataclasses.replace(
        entry, plan_in=plan(entry.plan_in, s_in),
        plan_out=plan(entry.plan_out, s_out),
        w_gate=weight(entry.w_gate, wg), w_up=weight(entry.w_up, wu),
        w_down=weight(entry.w_down, wd))


class Layer:
    """Set-up of one cell: weights from the seed, phase 1, the jitted step
    and the traffic's pool of inputs."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices):
        if len(devices) != 1:
            raise NotImplementedError("this system runs on one chip")
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.tokens_per_call = traffic["batch"] * traffic["seq"]
        mask, wg, wu, wd = inputs.make_weights(cfg, seed)
        params = {"w_gate": {"w": wg}, "w_up": {"w": wu},
                  "w_down": {"w": wd}, "block_mask": mask}
        t0 = time.perf_counter()
        comp = compress_ffn(params, tokens=self.tokens_per_call,
                            backend="pallas", block=cfg["block"])
        self.plan_build_s = time.perf_counter() - t0
        del params, mask, wg, wu, wd
        entry = comp.specialize(self.tokens_per_call)
        self.info = {
            "dataflows": [entry.plan_in.dataflow, entry.plan_out.dataflow],
            "dense_escape": any("dense" in p.aux
                                for p in (entry.plan_in, entry.plan_out)),
            "kept_blocks": int(entry.w_gate.data.shape[0]),
        }
        self._dyn = jax.device_put(_dynamic(entry))
        tokens = self.tokens_per_call

        def step(dyn, x):
            return sparse_ffn_apply(_Planned(_rebuild(entry, dyn), tokens), x)

        self._step = jax.jit(step)
        self.pool = inputs.make_pool(cfg, traffic, seed)
        b = cfg["block"]
        self.work = swiglu_ffn_work(
            tokens=tokens, d_model=cfg["hidden_size"],
            kept_elems_per_matrix=self.info["kept_blocks"] * b * b,
            weight_itemsize=jnp.dtype(entry.w_gate.data.dtype).itemsize,
            act_itemsize=jnp.dtype(self.pool[0].dtype).itemsize,
            out_itemsize=jnp.dtype(self.pool[0].dtype).itemsize)

    def call(self, x):
        """Issue one call; returns its (not yet ready) output."""
        return self._step(self._dyn, x)

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        self._dyn = self._step = self.pool = None


def build(cfg: dict, traffic: dict, seed: int, devices) -> Layer:
    return Layer(cfg, traffic, seed, devices)


def check(cfg: dict, seed: int, platform: str, answers: dict, xs: dict,
          *, control: bool = False) -> dict:
    """Compare the sampled answers with the reference, after the window.

    ``answers``/``xs``: pool slot -> the program's last in-window output
    for that slot / its input.  Returns readings: ``out_gap`` (the widest
    gap over the sampled answers), ``gaps`` (each answer's) and, with ``control``, the readings of
    the control and of a float32 ``HIGHEST`` reference, for setting limits.
    """
    d, b = cfg["hidden_size"], cfg["block"]
    slots = sorted(answers)
    x = jnp.concatenate([xs[s].reshape(-1, d) for s in slots], axis=0)
    y = jnp.concatenate([answers[s].reshape(-1, d) for s in slots], axis=0)
    rows = [xs[s].reshape(-1, d).shape[0] for s in slots]
    w = reference.masked_weights(*inputs.make_weights(cfg, seed), block=b)
    opd = reference.default_operand_dtype(platform)
    ref = reference.in_blocks(
        lambda xr, *ws: reference.swiglu(xr, *ws, operand_dtype=opd),
        x, w, cfg["reference_rows"])
    bounds = np.cumsum([0] + rows)

    def gaps(out, against):
        return [reference.widest_gap(out[i:j], against[i:j])
                for i, j in zip(bounds[:-1], bounds[1:])]

    def worst(out, against):
        return max(gaps(out, against))

    per_answer = gaps(y, ref)
    readings = {"out_gap": max(per_answer), "gaps": per_answer}
    if control:
        ctl = reference.in_blocks(reference.swiglu_lowp, x, w,
                                  cfg["reference_rows"])
        ref32 = reference.in_blocks(reference.swiglu, x, w,
                                    cfg["reference_rows"])
        readings.update(control_gap=worst(ctl, ref),
                        out_gap_f32=worst(y, ref32),
                        control_gap_f32=worst(ctl, ref32))
    return readings
