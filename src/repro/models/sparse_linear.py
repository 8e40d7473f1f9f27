"""Compressed sparse-FFN inference — the paper's technique end-to-end in a
model.

Training keeps block-masked dense weights (`ffn.py`); for serving, this
module runs phase 1 *once* per (token count, layer) through the plan API:

- phase 1: `compress_ffn` — builds :class:`repro.api.FlexagonPlan`s for each
  of the FFN's three matmuls (occupancy → selector → compression layout →
  index plans) and packs the weights into the planned formats;
- runtime: `sparse_ffn_apply` — pure plan.apply calls, jit-compatible, zero
  host-side re-planning.  A decode loop that admits new token shapes gets a
  shape-specialized plan from the per-FFN cache (`CompressedFFN.specialize`),
  built at admission and reused every subsequent step.

Observability (:mod:`repro.obs`): set-up runs under an ``ffn.compress``
span with ``ffn.mask``, ``plan.phase1`` and ``ffn.pack`` children, and
counts ``ffn.mask_s`` / ``ffn.pack_s`` (and, through the plan API,
``plan.build_s``) into the global registry; ``ffn.block_pairs`` holds the
block pairs one call of the last planned shape multiplies, and
``ffn.grid_steps`` the kernel grid steps it runs them in.  At run time
the matmuls run under the ``jax.named_scope`` names ``ffn.gate`` /
``ffn.up`` / ``ffn.down`` and the SiLU·mul under ``ffn.act``, which name
their device work in a profiler trace and cost nothing per call.

The activations-side operand is dense here (weights sparse × activations
dense), the SpMM special case of SpMSpM — `flexagon_plan` takes the bare
``(tokens, d)`` shape as a fully-dense pattern.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..api import FlexagonPlan, PlanCache, SparseOperand, flexagon_plan
from ..core.selector import TPUSpec
from .ffn import _masked_weight

__all__ = ["CompressedFFN", "PlannedFFN", "compress_ffn", "sparse_ffn_apply"]


@dataclasses.dataclass
class PlannedFFN:
    """Plans + packed weights for one token shape (phase-1 output)."""

    plan_in: FlexagonPlan        # x @ w_gate and x @ w_up  (same pattern)
    plan_out: FlexagonPlan       # h @ w_down
    w_gate: SparseOperand
    w_up: SparseOperand
    w_down: SparseOperand

    def block_pairs(self) -> Dict[str, Optional[int]]:
        """Effectual (weight, activation) block pairs one call multiplies,
        per matmul: each plan's stream-schedule work entries that are real.
        ``None`` for a plan without a stream schedule (reference backend,
        tiled or sharded plans)."""
        return self._per_matmul("n_real_work")

    def grid_steps(self) -> Dict[str, Optional[int]]:
        """Kernel grid steps one call runs, per matmul (the block-run
        kernel walks several block pairs a step); ``None`` as above."""
        return self._per_matmul("grid_steps")

    def _per_matmul(self, count: str) -> Dict[str, Optional[int]]:
        return {"gate": _schedule_count(self.plan_in, count),
                "up": _schedule_count(self.plan_in, count),
                "down": _schedule_count(self.plan_out, count)}


def _schedule_count(plan, count: str) -> Optional[int]:
    aux = getattr(plan, "aux", None)
    sched = aux.get("stream_schedule") if isinstance(aux, dict) else None
    return None if sched is None else getattr(sched, count)


class CompressedFFN:
    """One pruned FFN, planned per token shape and cached.

    ``specialize(tokens)`` is the admission-time hook: the first request for
    a token shape runs phase 1 (counted in ``plan_builds``); every subsequent
    request is a dictionary hit (``plan_hits``) — the plan-once / execute-many
    contract for serving loops.

    The underlying :class:`repro.api.FlexagonPlan`\\ s route through a
    (shareable, LRU-bounded) :class:`repro.api.PlanCache`; ``max_shapes``
    bounds the per-token-shape entries the FFN itself retains, so serving
    traffic with adversarial shape diversity cannot grow either level
    without limit.  ``cache_stats`` exposes the plan cache's
    hit/miss/eviction counters (surfaced by ``ServeEngine.stats``).
    """

    def __init__(self, w_gate: np.ndarray, w_up: np.ndarray,
                 w_down: np.ndarray, *, tokens: int, block: int = 128,
                 spec: TPUSpec = TPUSpec(), backend=None, policy=None,
                 memory_budget=None, mesh=None, partition=None,
                 plan_cache: Optional[PlanCache] = None,
                 max_shapes: Optional[int] = None,
                 verify: Optional[bool] = None):
        self._dense = (w_gate, w_up, w_down)    # masked dense, phase-1 only
        self.block = block
        self.spec = spec
        self.backend = backend                  # registry name / instance
        self.policy = policy                    # SelectionPolicy / name
        self.memory_budget = memory_budget      # repro.memory.MemoryBudget
        self.mesh = mesh                        # jax device mesh (repro.dist)
        self.partition = partition              # repro.dist.DistPartition
        self.verify = verify                    # plan-build verification gate
        self.tokens = tokens
        self.plan_cache = plan_cache if plan_cache is not None \
            else PlanCache(spec, maxsize=None if max_shapes is None
                           else 2 * max_shapes)
        self.max_shapes = max_shapes
        self._by_tokens: "OrderedDict[int, PlannedFFN]" = OrderedDict()
        self.shape_evictions = 0
        # packed weights are keyed by ("gate"|"up"|"down", planned B format):
        # the weight-side layout depends only on the weight pattern and the
        # format Table 3 assigns, so token shapes sharing a dataflow family
        # share one packed copy instead of one per token count
        self._packed: Dict[tuple, SparseOperand] = {}
        self.plan_builds = 0
        self.plan_hits = 0
        self.specialize(tokens)

    @property
    def cache_stats(self) -> Dict[str, Any]:
        """Plan-cache counters + this FFN's shape-level cache state."""
        stats = dict(self.plan_cache.stats)
        stats["shapes"] = len(self._by_tokens)
        stats["shape_evictions"] = self.shape_evictions
        return stats

    def _pack(self, which: str, w: np.ndarray, plan) -> SparseOperand:
        key = (which, plan.formats[1])
        packed = self._packed.get(key)
        if packed is None:
            t0 = obs.now_ns()
            with obs.span("ffn.pack", which=which):
                packed = jax.block_until_ready(plan.pack_b(w))
            obs.get_registry().histogram("ffn.pack_s").observe(
                (obs.now_ns() - t0) / 1e9)
            self._packed[key] = packed
        return packed

    def specialize(self, tokens: int) -> PlannedFFN:
        """Plans for this token count — built once, then cache hits."""
        entry = self._by_tokens.get(tokens)
        if entry is not None:
            self.plan_hits += 1
            self._by_tokens.move_to_end(tokens)
            return entry
        wg, wu, wd = self._dense
        d, f = wg.shape
        bs = (self.block, self.block, self.block)
        plan_in = self.plan_cache.get((tokens, d), wg, block_shape=bs,
                                      backend=self.backend,
                                      policy=self.policy,
                                      memory_budget=self.memory_budget,
                                      mesh=self.mesh,
                                      partition=self.partition,
                                      verify=self.verify)
        plan_out = self.plan_cache.get((tokens, f), wd, block_shape=bs,
                                       backend=self.backend,
                                       policy=self.policy,
                                       memory_budget=self.memory_budget,
                                       mesh=self.mesh,
                                       partition=self.partition,
                                       verify=self.verify)
        entry = PlannedFFN(plan_in, plan_out,
                           self._pack("gate", wg, plan_in),
                           self._pack("up", wu, plan_in),
                           self._pack("down", wd, plan_out))
        for gauge, counts in (("ffn.block_pairs", entry.block_pairs()),
                              ("ffn.grid_steps", entry.grid_steps())):
            if None not in counts.values():
                obs.get_registry().gauge(gauge).set(sum(counts.values()))
        self._by_tokens[tokens] = entry
        self.plan_builds += 1
        if self.max_shapes is not None \
                and len(self._by_tokens) > self.max_shapes:
            self._by_tokens.popitem(last=False)
            self.shape_evictions += 1
        return entry

    # -- conveniences over the default (construction-time) token shape ----
    @property
    def _default(self) -> PlannedFFN:
        entry = self._by_tokens.get(self.tokens)
        if entry is None:               # evicted under max_shapes: replan
            entry = self.specialize(self.tokens)
        return entry

    @property
    def w_gate(self) -> SparseOperand:
        return self._default.w_gate

    @property
    def w_up(self) -> SparseOperand:
        return self._default.w_up

    @property
    def w_down(self) -> SparseOperand:
        return self._default.w_down

    @property
    def dataflow_in(self) -> str:
        return self._default.plan_in.dataflow

    @property
    def dataflow_out(self) -> str:
        return self._default.plan_out.dataflow


def compress_ffn(ffn_params: Dict[str, Any], *, tokens: int,
                 block: int = 128, spec: TPUSpec = TPUSpec(),
                 backend=None, policy=None, memory_budget=None,
                 mesh=None, partition=None,
                 plan_cache: Optional[PlanCache] = None,
                 max_shapes: Optional[int] = None,
                 verify: Optional[bool] = None) -> CompressedFFN:
    """Phase 1 for one pruned FFN layer: occupancy → dataflow → plans.

    ``backend``/``policy`` parameterize the plan API's execution substrate
    and selection strategy (see :mod:`repro.backends`); ``memory_budget``
    auto-tiles over-budget matmuls (see :mod:`repro.memory`);
    ``mesh``/``partition`` shard every plan across a device mesh (see
    :mod:`repro.dist` — the fused-decode matmuls then run as one
    ``shard_map``); ``plan_cache``/``max_shapes`` bound the serving-loop
    plan caches; ``verify`` gates every plan build behind
    ``repro.analysis.verify_plan`` (``None`` defers to ``REPRO_VERIFY``).
    """
    assert "block_mask" in ffn_params, "FFN is not block-pruned"
    mask = ffn_params["block_mask"]
    with obs.span("ffn.compress", tokens=tokens):
        t0 = obs.now_ns()
        with obs.span("ffn.mask"):
            wg = np.asarray(_masked_weight(ffn_params["w_gate"]["w"], mask))
            wu = np.asarray(_masked_weight(ffn_params["w_up"]["w"], mask))
            wd = np.asarray(_masked_weight(ffn_params["w_down"]["w"],
                                           mask.T))
        obs.get_registry().histogram("ffn.mask_s").observe(
            (obs.now_ns() - t0) / 1e9)
        return CompressedFFN(wg, wu, wd, tokens=tokens, block=block,
                             spec=spec, backend=backend, policy=policy,
                             memory_budget=memory_budget, mesh=mesh,
                             partition=partition, plan_cache=plan_cache,
                             max_shapes=max_shapes, verify=verify)


def sparse_ffn_apply(comp: CompressedFFN, x: jax.Array) -> jax.Array:
    """x: (B, S, D) -> (B, S, D) via the compressed, dataflow-planned FFN."""
    b, s, d = x.shape
    entry = comp.specialize(b * s)          # cache hit on steady-state shapes
    x2d = x.reshape(b * s, d).astype(jnp.float32)
    # the scopes name each matmul's device work; the order of the
    # operations is the scheduler's starting point, so it stays as it was
    with jax.named_scope("ffn.gate"):
        g = entry.plan_in.apply(x2d, entry.w_gate)
    with jax.named_scope("ffn.act"):
        g = jax.nn.silu(g)
    with jax.named_scope("ffn.up"):
        u = entry.plan_in.apply(x2d, entry.w_up)
    with jax.named_scope("ffn.act"):
        h = g * u
    with jax.named_scope("ffn.down"):
        y = entry.plan_out.apply(h, entry.w_down)
    return y.reshape(b, s, d).astype(x.dtype)
