"""System under test: a stack of pruned FFN sublayers, one dense then MoE
layers, each ``x <- x + FFN(RMSNorm(x))``, run as one jitted program
through ``repro.models.sparse_moe``.

The dense layer and each MoE layer's shared expert are planned by
``compress_ffn`` and run by ``sparse_ffn_apply``; each MoE layer's held
experts are planned and packed together by ``compress_experts`` and routed,
dispatched, run and combined by ``moe_block_apply``.  As in
``pruned_swiglu_ffn``, the seed-dependent arrays (packed weight blocks,
schedules, router weights) are the jitted program's arguments, so one
compile serves every seed; plans, packing, routing, kernels and arithmetic
are the program's.

Each call returns the stack's output, the experts each MoE layer selected
and, per layer and held expert, its live rows and executed slots.  The
window keeps the counts on the device and reads them after it ends.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.sparse_linear import compress_ffn
from repro.models.sparse_moe import (RouterSpec, compress_experts,
                                     ffn_block_apply, moe_block_apply)

from .. import inputs_moe, reference, reference_moe
from ..work_moe import MoEWork
from .pruned_swiglu_ffn import _Planned, _dynamic, _rebuild

ROOT = Path(__file__).resolve().parents[2]


class Answer:
    """One call's outputs: the stack's output ``out`` (batch, seq, d), the
    selected experts ``ids`` (MoE layers, tokens, top_k) and ``counts``
    (MoE layers, 2, held experts): live rows, executed slots."""

    def __init__(self, out, ids, counts):
        self.out, self.ids, self.counts = out, ids, counts

    def block_until_ready(self):
        jax.block_until_ready((self.out, self.ids, self.counts))
        return self


def router_spec(cfg: dict) -> RouterSpec:
    return RouterSpec(n_experts=inputs_moe.routed_total(cfg),
                      n_group=cfg["n_group"], topk_group=cfg["topk_group"],
                      top_k=cfg["num_experts_per_tok"],
                      scale=float(cfg["routed_scaling_factor"]))


def _planned_ffn(parts, tokens: int, block: int):
    mask, wg, wu, wd = parts
    params = {"w_gate": {"w": wg}, "w_up": {"w": wu}, "w_down": {"w": wd},
              "block_mask": mask}
    entry = compress_ffn(params, tokens=tokens, backend="pallas",
                         block=block).specialize(tokens)
    return entry, jax.device_put(_dynamic(entry))


class Layer:
    """Set-up of one cell: weights from the seed, phase 1 for every layer,
    the jitted stack and the traffic's pool of inputs."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices):
        if len(devices) != 1:
            raise NotImplementedError("this system runs on one chip")
        self.tokens_per_call = tokens = traffic["batch"] * traffic["seq"]
        b, eps = cfg["block"], cfg["rms_norm_eps"]
        self.pool = inputs_moe.make_pool(cfg, traffic, seed)
        spec = router_spec(cfg)
        held_lo = inputs_moe.held(cfg).start
        t0 = time.perf_counter()
        dense = [_planned_ffn(inputs_moe.dense_ffn(cfg, seed), tokens, b)
                 for _ in range(cfg["dense_layers"])]
        moe = []
        for layer in range(1, cfg["moe_layers"] + 1):
            shared = _planned_ffn(inputs_moe.shared_ffn(cfg, seed, layer),
                                  tokens, b)
            experts = compress_experts(
                inputs_moe.expert_masks(cfg, seed, layer),
                lambda lo, hi, layer=layer: inputs_moe.experts(
                    cfg, seed, layer, lo, hi), block=b)
            moe.append((shared, experts, inputs_moe.router(cfg, seed, layer)))
        self.plan_build_s = time.perf_counter() - t0
        ones = inputs_moe.norm(cfg)
        dyn = {"dense": [d for _, d in dense],
               "moe": [(s[1], e, r) for s, e, r in moe]}
        dense_entries = [e for e, _ in dense]
        shared_entries = [s[0] for s, _, _ in moe]

        def step(dyn, x):
            for entry, d in zip(dense_entries, dyn["dense"]):
                x = ffn_block_apply(x, ones, _Planned(_rebuild(entry, d),
                                                      tokens), eps=eps)
            ids, counts = [], []
            for entry, (s, experts, (w_r, bias)) in zip(shared_entries,
                                                        dyn["moe"]):
                x, i, c = moe_block_apply(
                    x, ones, w_r, bias, experts,
                    _Planned(_rebuild(entry, s), tokens), spec, eps=eps,
                    held_lo=held_lo)
                ids.append(i)
                counts.append(c)
            return x, jnp.stack(ids), jnp.stack(counts)

        self._dyn = dyn
        self._step = jax.jit(step)
        first = moe[0][1]
        self.info = {
            "dataflows": [dense_entries[0].plan_in.dataflow,
                          dense_entries[0].plan_out.dataflow],
            "held_experts": first.n_experts,
            "expert_pairs_per_slot": first.pairs_per_slot(),
        }
        self._counts = []
        self.work = MoEWork.of_config(cfg, self._counts)

    def call(self, x):
        """Issue one call; returns its (not yet ready) :class:`Answer`."""
        out, ids, counts = self._step(self._dyn, x)
        self._counts.append(counts)
        return Answer(out, ids, counts)

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        self._dyn = self._step = self.pool = None


def build(cfg: dict, traffic: dict, seed: int, devices) -> Layer:
    return Layer(cfg, traffic, seed, devices)


def route_limits(cfg: dict, root: Path = ROOT) -> dict:
    """The route limits of the cells that run ``cfg`` (the strictest of
    each, if several do), from their ``bench/limits/<cell>.json``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    found = [json.loads((root / "bench" / "limits" /
                         f"{w['name']}.json").read_text())
             for w in spec["workloads"] if w["config"] == cfg["name"]]
    if not found:
        raise KeyError(f"no cell runs configuration {cfg['name']!r}")
    return {k: min(f[k] for f in found)
            for k in ("route_eps", "route_mismatch_share")}


def _route_readings(routes, limits: dict, shown: int = 8) -> dict:
    """Mismatches of the program's routes against the reference's, with the
    first ``shown`` as [MoE layer, row, group gap, expert gap]."""
    differ, group_gap, expert_gap = (
        np.stack([np.asarray(r[i]) for r in routes]) for i in (1, 2, 3))
    gaps = np.maximum(group_gap, expert_gap)
    share = float(differ.mean())
    gap_max = float(gaps[differ].max()) if differ.any() else 0.0
    ok = share <= limits["route_mismatch_share"] and \
        gap_max <= limits["route_eps"]
    where = np.argwhere(differ)[:shown]
    return {"route_mismatch": int(differ.sum()),
            "route_token_layers": int(differ.size),
            "route_mismatch_share": share, "route_gap_max": gap_max,
            "route_mismatches": [[int(layer), int(row),
                                  float(group_gap[layer, row]),
                                  float(expert_gap[layer, row])]
                                 for layer, row in where],
            "routes_ok": ok}


def check(cfg: dict, seed: int, platform: str, answers: dict, xs: dict,
          *, control: bool = False, limits: dict | None = None) -> dict:
    """Compare the sampled answers with the reference, after the window.

    ``answers``/``xs``: pool slot -> the program's last in-window
    :class:`Answer` for that slot / its input.  The reference runs on the
    program's routes (the experts each answer's call selected) and, at
    every MoE layer, selects on its own scores too.  A token-layer whose selection differs must be a near-tie (gap
    at most ``route_eps``, ``reference_moe.tie_gaps``), and at most a
    ``route_mismatch_share`` of them may differ; otherwise ``out_gap``
    reads infinite.  ``limits`` defaults to the route limits of the cells
    that run ``cfg``.  Returns ``out_gap`` (the widest gap over the
    sampled answers), ``gaps`` (each answer's), ``answer_gap`` (the widest
    gap whatever the routes) and the route readings;
    with ``control``, also the control's (every array in bfloat16): its
    ``control_gap`` on the program's routes, and its own routes' readings.
    """
    limits = route_limits(cfg) if limits is None else limits
    d = cfg["hidden_size"]
    slots = sorted(answers)
    x = jnp.concatenate([xs[s].reshape(-1, d) for s in slots], axis=0)
    y = jnp.concatenate([answers[s].out.reshape(-1, d) for s in slots])
    ids = np.concatenate([np.asarray(answers[s].ids) for s in slots], axis=1)
    rows = [xs[s].reshape(-1, d).shape[0] for s in slots]
    bounds = np.cumsum([0] + rows)
    opd = reference.default_operand_dtype(platform)
    ref, routes = reference_moe.forward(cfg, seed, x, forced=ids,
                                        operand_dtype=opd)

    def gaps(out):
        return [reference.widest_gap(out[i:j], ref[i:j])
                for i, j in zip(bounds[:-1], bounds[1:])]

    readings = _route_readings(routes, limits)
    answer_gap = gaps(y)
    per_answer = answer_gap if readings["routes_ok"] else \
        [float("inf")] * len(answer_gap)
    readings.update(out_gap=max(per_answer), gaps=per_answer,
                    answer_gap=max(answer_gap))
    if control:
        # the control's answers on the program's routes, and its own routes
        # held against the reference's scores as the program's are
        ctl, _ = reference_moe.forward(cfg, seed, x, forced=ids, lowp=True)
        _, own = reference_moe.forward(cfg, seed, x, lowp=True)
        _, ctl_routes = reference_moe.forward(
            cfg, seed, x, forced=[r[0] for r in own], operand_dtype=opd)
        readings.update(control_gap=max(gaps(ctl)), **{
            f"control_{k}": v
            for k, v in _route_readings(ctl_routes, limits).items()})
    return readings
