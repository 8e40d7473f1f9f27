"""Serving engine: batched prefill/decode with continuous batching.

Slot-based design (vLLM-lite): the engine owns a fixed-batch KV cache; each
slot holds one in-flight request.  New requests prefill into a free slot (a
batch-1 prefill written into the slot's cache lines); every ``step()`` runs
one fused decode for all active slots; finished sequences free their slot for
queued requests.  Greedy sampling by default.

Phase 1 runs at admission, not per step (the plan-once / execute-many
contract of :mod:`repro.api`):

- MoE models get their dispatch strategy planned once for the fused decode
  shape via :func:`repro.models.moe.plan_moe`, and the decode closure is
  jitted against a model whose config pins that strategy — decode steps
  skip the per-call selector entirely (at decode, token counts are tiny so
  the Gust-analogue (sort) or OP-analogue (scatter) dispatch wins over the
  capacity einsum).  The decode token count is always ``slots``, so the
  pinned choice equals what "auto" would re-derive every step.  Prefill
  keeps the unpinned model (its shapes vary per prompt).
- a pruned-FFN model passes its :class:`repro.models.sparse_linear
  .CompressedFFN`; the engine specializes it for the fused decode shape
  (``slots`` tokens, exposed as ``decode_ffn``) at construction and for
  each new prefill length at admission, so a model routing its FFN through
  ``sparse_ffn_apply`` only ever hits cached plans
  (``stats["plan_builds"]`` / ``stats["plan_hits"]``; the underlying
  LRU :class:`repro.api.PlanCache`'s hit/miss/eviction counters surface
  as ``stats["plan_cache"]``).

All phase-1 machinery runs through the pluggable plan surface
(:mod:`repro.backends`): the sparse FFN's plans execute on whatever backend
the ``CompressedFFN`` was built with (reported in ``stats["backend"]``), and
``moe_policy=`` swaps the MoE dispatch selector for a dataflow
:class:`repro.backends.SelectionPolicy` — the engine itself never touches a
kernel.  A ``CompressedFFN`` built with a ``mesh=`` runs the fused decode
*sharded* — each decode-shape plan is a :class:`repro.dist.ShardedPlan`
whose ``shard_map`` the jitted decode closure traces straight through, and
``stats["dist"]`` reports the mesh shape, shard count, and collective-merge
(ICI) bytes.

Telemetry goes through :mod:`repro.obs`: each engine owns a
:class:`repro.obs.MetricsRegistry` (``serve.prefills`` / ``decode_steps`` /
``completed`` counters, ``serve.latency.{queue_s,prefill_s,decode_step_s,
request_s}`` histograms — summaries via :meth:`ServeEngine.latency_stats`),
and with ``REPRO_TRACE`` enabled every request emits an admit→complete
``serve.request`` span whose children (``serve.prefill``, and the
``plan.phase1`` spans of any admission-time planning) reconstruct the
request tree in Perfetto.  ``ServeEngine.stats`` is now a snapshot
*property* over the registry — same keys as the historical dict, but every
read is an independent deep copy.
"""
from __future__ import annotations

import copy
import dataclasses
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..models.moe import MoEPlan, plan_moe

__all__ = ["Request", "ServeEngine"]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (S,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    # obs bookkeeping (admit→complete span + queue/request latency)
    t_submit_ns: Optional[int] = None
    t_admit_ns: Optional[int] = None
    span_id: Optional[int] = None

    @property
    def done(self) -> bool:
        if self.eos_id is not None and self.out_tokens \
                and self.out_tokens[-1] == self.eos_id:
            return True
        return len(self.out_tokens) >= self.max_new_tokens


class ServeEngine:
    def __init__(self, model, params, *, slots: int = 4, max_seq: int = 256,
                 dtype=jnp.bfloat16, sparse_ffn=None, moe_policy=None,
                 verify: Optional[bool] = None):
        # ``verify`` gates every plan the engine builds (construction-time
        # decode plans and admission-time prefill plans) behind
        # ``repro.analysis.verify_plan``; None defers to REPRO_VERIFY
        self.model = model
        if sparse_ffn is not None and verify is not None:
            sparse_ffn.verify = verify
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        # the cache dtype is part of the engine's contract: prefill builds
        # its batch-1 caches with the same dtype, so prefill compute and the
        # slot write agree (no silent default-dtype prefill + cast-at-write)
        self.dtype = dtype
        self.cache = model.init_cache(slots, max_seq, dtype)
        self._free = deque(range(slots))
        self._active: Dict[int, Request] = {}
        self._queue: deque = deque()
        self._finished: List[Request] = []
        self._positions = np.zeros(slots, np.int64)
        # Telemetry lives in a per-engine MetricsRegistry (serve.* counters
        # + serve.latency.* histograms); ``stats`` is a read-only snapshot
        # property over it, so two engines in one process never share
        # counters and callers keep the historical dict shape.
        self.metrics = obs.MetricsRegistry()
        self._plan_stats: Dict[str, Any] = {"plan_builds": 0, "plan_hits": 0}
        # phase 1 for the steady state, up front: the fused decode step
        # always runs `slots` tokens, so its plans never change after this
        self.sparse_ffn = sparse_ffn
        self.decode_ffn = None
        if sparse_ffn is not None:
            self.decode_ffn = sparse_ffn.specialize(slots)
        self.moe_plan: Optional[MoEPlan] = None
        decode_model = model
        cfg = getattr(model, "cfg", None)
        if cfg is not None and getattr(cfg, "moe", None) is not None \
                and cfg.moe.strategy == "auto":
            self.moe_plan = plan_moe(cfg, slots, policy=moe_policy)
            pinned = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe,
                                             strategy=self.moe_plan.strategy))
            decode_model = type(model)(pinned)
        self._decode = jax.jit(decode_model.decode_step)
        self._sync_plan_stats()

    @property
    def stats(self) -> Dict[str, Any]:
        """Point-in-time telemetry snapshot (historical dict shape).

        Served from the per-engine :class:`repro.obs.MetricsRegistry` plus
        the last plan-stats sync; every call returns a fresh **deep copy**,
        so mutating a nested dict on the policy/cache after a snapshot was
        taken cannot rewrite history (regression-tested in tests/test_serve
        and tests/test_obs).
        """
        m = self.metrics
        out: Dict[str, Any] = {
            "prefills": int(m.value("serve.prefills")),
            "decode_steps": int(m.value("serve.decode_steps")),
            "completed": int(m.value("serve.completed")),
        }
        out.update(copy.deepcopy(self._plan_stats))
        return out

    def latency_stats(self) -> Dict[str, Dict[str, Any]]:
        """``serve.latency.*`` histogram summaries (count/p50/p90/p99)."""
        return self.metrics.snapshot(prefix="serve.latency.")

    def verify_plans(self) -> List[Any]:
        """Audit every plan currently cached for serving (DESIGN.md §19).

        Runs :func:`repro.analysis.verify_cache` — the full plan
        invariants *plus* the static schedule checker — over the sparse
        FFN's LRU as it stands now, so a serving loop can prove that
        re-admitted/re-targeted entries (not just original insertions)
        still carry race-free, in-bounds, deterministic schedules.
        Returns the diagnostics ([] for engines without a sparse FFN).
        """
        if self.sparse_ffn is None:
            return []
        from ..analysis import verify_cache

        return list(verify_cache(self.sparse_ffn.plan_cache))

    def _sync_plan_stats(self):
        if self.sparse_ffn is not None:
            ps = self._plan_stats
            ps["plan_builds"] = self.sparse_ffn.plan_builds
            ps["plan_hits"] = self.sparse_ffn.plan_hits
            backend = self.sparse_ffn.backend
            ps["backend"] = (backend if isinstance(backend, str)
                             else getattr(backend, "name", None)) \
                or "reference"
            # LRU plan-cache behaviour under serving traffic
            # (hit/miss/eviction counters, DESIGN.md §12).  Deep-copied:
            # these are live nested dicts owned by the policy/cache and
            # must not alias into snapshots.
            cache_stats = getattr(self.sparse_ffn, "cache_stats", None)
            if cache_stats is not None:
                ps["plan_cache"] = copy.deepcopy(cache_stats)
            # selection-policy telemetry (autotune hit/miss/measurement
            # counters — DESIGN.md §11)
            pol = getattr(self.sparse_ffn, "policy", None)
            if pol is not None:
                pol_stats = getattr(pol, "stats", None)
                ps["policy"] = (copy.deepcopy(pol_stats)
                                if isinstance(pol_stats, dict)
                                else {"name": str(pol)})
            # sharded fused decode: shard / collective telemetry from the
            # decode-shape plans (DESIGN.md §13)
            entry = self.decode_ffn
            if entry is not None:
                dist = [p.dist_stats for p in (entry.plan_in, entry.plan_out)
                        if hasattr(p, "dist_stats")]
                if dist:
                    ici = float(sum(d["ici_bytes"] for d in dist))
                    ps["dist"] = {
                        "mesh_shape": dist[0]["mesh_shape"],
                        "shards": dist[0]["shards"],
                        "collectives": sum(1 for d in dist
                                           if d["collective"] == "psum"),
                        "ici_bytes": ici,
                    }
                    obs.get_registry().gauge("dist.ici_bytes").set(ici)

    # -- request lifecycle ---------------------------------------------------
    def submit(self, req: Request):
        req.t_submit_ns = obs.now_ns()
        self._queue.append(req)
        self._admit()

    def _admit(self):
        while self._queue and self._free:
            req = self._queue.popleft()
            slot = self._free.popleft()
            req.slot = slot
            req.t_admit_ns = obs.now_ns()
            if req.t_submit_ns is not None:
                self.metrics.histogram("serve.latency.queue_s").observe(
                    (req.t_admit_ns - req.t_submit_ns) / 1e9)
            if obs.enabled():
                # the admit→complete request span is recorded at completion
                # (it outlives any `with` block); children parent onto its
                # pre-allocated id so the tree survives interleaved steps
                req.span_id = obs.get_tracer().new_id()
            self._prefill_into_slot(req)
            self._active[slot] = req

    def _prefill_into_slot(self, req: Request):
        """Batch-1 prefill, written into this slot's cache lines.

        Admission is where new shapes appear, so phase 1 for this prompt
        length runs here (cached — repeat lengths are hits, and the decode
        shape was planned at construction)."""
        t0 = obs.now_ns()
        model = self.model
        if self.sparse_ffn is not None:
            self.sparse_ffn.specialize(len(req.prompt))
            self._sync_plan_stats()
        one_cache = model.init_cache(1, self.max_seq, self.dtype)
        tokens = jnp.asarray(req.prompt, jnp.int32)[None]
        logits, one_cache = model.prefill(self.params, tokens, one_cache)
        next_tok = int(jnp.argmax(logits[0, -1]))
        req.out_tokens.append(next_tok)
        self._write_slot(req.slot, one_cache)
        self._set_pos(req.slot, len(req.prompt))
        dur = obs.now_ns() - t0
        if req.span_id is not None:
            # child of the request's pre-allocated admit→complete span
            obs.get_tracer().record(
                "serve.prefill", t0, dur, parent=req.span_id,
                attrs={"rid": req.rid, "slot": req.slot,
                       "prompt_len": len(req.prompt)})
        self.metrics.counter("serve.prefills").inc()
        self.metrics.histogram("serve.latency.prefill_s").observe(dur / 1e9)

    def _write_slot(self, slot: int, one_cache, replace_full: bool = True):
        """Write every leaf of a batch-1 cache into this slot's cache lines.

        An unmatched non-scalar leaf is a hard error: silently skipping the
        write would leave the slot decoding against a stale/zero prefix with
        no signal at all (the exact failure mode the loud path prevents).
        ``replace_full=False`` leaves shape-identical leaves untouched
        instead of replacing them — a leaf with the same shape at batch 1
        and batch ``slots`` is slot-independent, and a slot reset must not
        clobber it for the still-active slots.
        """

        def write(full, one):
            if one.ndim == 0:
                return full
            if one.shape == full.shape:      # slots == 1: replace outright
                return one.astype(full.dtype) if replace_full else full
            # batch dim = the unique dim where full is `slots` wide and the
            # batch-1 cache is 1 wide, with all other dims matching
            cands = [d for d in range(full.ndim)
                     if full.shape[d] == self.slots and one.shape[d] == 1
                     and full.shape[:d] == one.shape[:d]
                     and full.shape[d + 1:] == one.shape[d + 1:]]
            if not cands:
                raise ValueError(
                    f"cannot locate the batch dim of cache leaf with shape "
                    f"{tuple(one.shape)} against slot cache leaf "
                    f"{tuple(full.shape)} (slots={self.slots}); refusing to "
                    "skip the write — the slot would decode against a "
                    "stale prefix")
            b_idx = cands[0]
            idx = [slice(None)] * full.ndim
            idx[b_idx] = slot
            return full.at[tuple(idx)].set(
                jnp.squeeze(one, b_idx).astype(full.dtype))

        layers = jax.tree.map(write, self.cache["layers"],
                              one_cache["layers"]) \
            if "layers" in self.cache else None
        if layers is not None:
            self.cache["layers"] = layers
        else:  # encdec caches are flat dicts
            for k in self.cache:
                if k in ("pos", "mem_len"):
                    continue
                self.cache[k] = write(self.cache[k], one_cache[k])

    def _set_pos(self, slot: int, value: int):
        pos = np.asarray(self.cache["pos"]).copy()
        pos[slot] = value
        self.cache["pos"] = jnp.asarray(pos, jnp.int32)
        self._positions[slot] = value

    def _reset_slot(self, slot: int):
        """Return a freed slot to the deterministic zero state.

        The fused decode keeps running over free slots (the batch shape is
        fixed), so without a reset a freed slot's cache lines and ``pos``
        would drift with however long it sat idle — reused-slot decode
        correctness would rest on prefill happening to overwrite every
        leaf.  Zeroing cache + pos on free (and re-pinning ``pos`` after
        every fused step) makes slot state independent of slot history.
        ``replace_full`` only with one slot total: a shape-identical leaf
        is slot-independent and must survive the reset for the still-active
        slots, but with a single slot whole-leaf zeroing *is* the reset.
        """
        self._write_slot(slot, self.model.init_cache(1, self.max_seq,
                                                     self.dtype),
                         replace_full=self.slots == 1)
        self._set_pos(slot, 0)

    def _complete_request(self, req: Request):
        """Close out a finished request's telemetry (admit→complete)."""
        t_end = obs.now_ns()
        if req.t_admit_ns is not None:
            self.metrics.histogram("serve.latency.request_s").observe(
                (t_end - req.t_admit_ns) / 1e9)
        if req.span_id is not None:
            # the root of this request's span tree: serve.prefill (and any
            # plan.* spans under it) recorded with parent=req.span_id
            obs.get_tracer().record(
                "serve.request", req.t_admit_ns, t_end - req.t_admit_ns,
                sid=req.span_id,
                attrs={"rid": req.rid, "slot": req.slot,
                       "prompt_len": len(req.prompt),
                       "new_tokens": len(req.out_tokens)})

    # -- decode loop -----------------------------------------------------------
    def step(self) -> List[Tuple[int, int]]:
        """One fused decode for all active slots; returns (rid, token) pairs."""
        if not self._active:
            return []
        t0 = obs.now_ns()
        toks = np.zeros((self.slots, 1), np.int32)
        for slot, req in self._active.items():
            toks[slot, 0] = req.out_tokens[-1]
        # per-slot positions (vector pos in the cache): mixed-progress slots
        # decode correctly in one fused step — continuous batching
        with obs.span("serve.decode_step", active=len(self._active)):
            logits, cache = self._decode(self.params, self.cache,
                                         jnp.asarray(toks))
        self.cache = cache
        self.metrics.counter("serve.decode_steps").inc()
        self.metrics.histogram("serve.latency.decode_step_s").observe(
            (obs.now_ns() - t0) / 1e9)
        out = []
        finished = []
        for slot, req in list(self._active.items()):
            nxt = int(jnp.argmax(logits[slot, -1]))
            req.out_tokens.append(nxt)
            self._positions[slot] += 1
            out.append((req.rid, nxt))
            if req.done:
                finished.append(slot)
        for slot in finished:
            self.metrics.counter("serve.completed").inc()
            req = self._active[slot]
            self._complete_request(req)
            self._finished.append(req)
            del self._active[slot]
            self._free.append(slot)
            self._reset_slot(slot)
        # free slots rode the fused step too (the batch shape is fixed, so
        # their lane is dead compute); undo the pos side effect so an idle
        # slot's state cannot drift between occupancies.  Stays on device —
        # no host round trip on the hot decode path.
        if len(self._active) < self.slots:
            active = np.zeros(self.slots, bool)
            active[list(self._active)] = True
            self.cache["pos"] = jnp.where(jnp.asarray(active),
                                          self.cache["pos"], 0)
        self._admit()
        return out

    def run_to_completion(self, max_steps: int = 1024) -> Dict[int, List[int]]:
        results: Dict[int, List[int]] = {}

        def harvest():
            for req in self._finished:
                results[req.rid] = req.out_tokens
            self._finished.clear()

        for _ in range(max_steps):
            if not self._active and not self._queue:
                break
            self.step()
            harvest()
        harvest()
        for req in list(self._active.values()):
            results[req.rid] = req.out_tokens
        return results
