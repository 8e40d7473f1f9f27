"""Jaxpr-level purity / cost analysis of ``plan.apply`` (DESIGN.md §15).

``trace_report(plan)`` traces the plan's phase-2 executor with
``jax.make_jaxpr`` over abstract inputs (no device work, no FLOPs) and
statically certifies the properties the serving path depends on:

- **purity** — zero host-callback primitives (``pure_callback``,
  ``io_callback``, ``debug_callback`` …) anywhere in the jaxpr, including
  nested ``scan``/``while``/``pjit`` bodies.  A callback would force a host
  round-trip per decode step;
- **cost cross-check** — FLOPs counted from ``dot_general`` equations
  (scan bodies multiplied by their trip count) compared against the phase-1
  roofline estimate (``plan.estimate.flops``); disagreement beyond 2×
  either way is flagged as a ``traffic-disagreement`` warning — the
  selector prices dataflows off that estimate, so a bad model silently
  picks bad dataflows;
- **retrace identity** — a stable ``aval_hash`` over the traced jaxpr and
  its abstract in/out types.  Two applies of the *same* cached plan must
  hash identically; :class:`RetraceDetector` turns that into a check over
  repeated :class:`repro.api.PlanCache` hits.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
from collections import Counter
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .diagnostics import ERROR, WARNING, PlanDiagnostic

__all__ = ["TraceReport", "trace_report", "RetraceDetector", "Observation",
           "IndexMapReport", "index_map_report"]

#: Primitive names that imply a host round-trip inside traced code.
HOST_CALLBACK_PRIMITIVES = frozenset({
    "pure_callback",
    "io_callback",
    "callback",
    "debug_callback",
    "debug_print",
    "host_callback_call",
    "outside_call",
    "python_callback",
})


@dataclasses.dataclass(frozen=True)
class TraceReport:
    """Static summary of one ``plan.apply`` trace."""

    jaxpr: Any                        # the ClosedJaxpr itself
    primitives: Dict[str, int]        # primitive name -> (trip-weighted) count
    callbacks: Tuple[str, ...]        # host-callback primitives found
    flops: float                      # dot_general FLOPs, trip-weighted
    bytes: float                      # materialized eqn-output bytes
    aval_hash: str                    # sha1 over jaxpr text + in/out avals
    diagnostics: Tuple[PlanDiagnostic, ...]

    @property
    def pure(self) -> bool:
        return not self.callbacks


def _aval_nbytes(aval) -> float:
    try:
        return float(np.prod(aval.shape, dtype=np.float64)
                     * np.dtype(aval.dtype).itemsize)
    except (AttributeError, TypeError):
        return 0.0


def _dot_flops(eqn) -> float:
    (lhs_contract, _), _ = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval
    contracted = 1.0
    for d in lhs_contract:
        contracted *= lhs.shape[d]
    out = eqn.outvars[0].aval
    return 2.0 * float(np.prod(out.shape, dtype=np.float64)) * contracted


def _sub_jaxprs(params) -> List[Tuple[Any, float]]:
    """(jaxpr, trip_multiplier) pairs nested in an equation's params."""
    out: List[Tuple[Any, float]] = []
    length = float(params.get("length", 1) or 1)
    for name, value in params.items():
        mult = length if name in ("jaxpr", "body_jaxpr") else 1.0
        candidates = value if isinstance(value, (list, tuple)) else (value,)
        for cand in candidates:
            core = getattr(cand, "jaxpr", None)
            if core is not None and hasattr(core, "eqns"):
                out.append((core, mult))
            elif hasattr(cand, "eqns"):
                out.append((cand, mult))
    return out


def _walk(jaxpr, primitives: Counter, callbacks: Counter,
          costs: List[float], weight: float) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        primitives[name] += int(weight) if weight >= 1 else 1
        if name in HOST_CALLBACK_PRIMITIVES:
            callbacks[name] += 1
        if name == "dot_general":
            costs[0] += weight * _dot_flops(eqn)
        for out in eqn.outvars:
            costs[1] += weight * _aval_nbytes(getattr(out, "aval", None))
        for sub, mult in _sub_jaxprs(eqn.params):
            # a while body's trip count is data-dependent: count it once
            sub_w = weight * (mult if name != "while" else 1.0)
            _walk(sub, primitives, callbacks, costs, sub_w)


def trace_report(plan: Any, out_dtype=jnp.float32,
                 in_dtype=jnp.float32) -> TraceReport:
    """Trace ``plan.apply`` abstractly and report purity, cost, identity."""
    if not hasattr(plan, "apply") or not hasattr(plan, "shapes"):
        raise TypeError(f"{type(plan).__name__} has no traceable apply; "
                        "trace_report covers FlexagonPlan/TiledPlan/"
                        "ShardedPlan")
    m, k, n = plan.shapes

    def _apply(a, b):
        return plan.apply(a, b, out_dtype)

    try:
        closed = jax.make_jaxpr(_apply)(
            jax.ShapeDtypeStruct((m, k), in_dtype),
            jax.ShapeDtypeStruct((k, n), in_dtype))
    except TypeError:
        # some jax versions want concrete arrays for make_jaxpr
        closed = jax.make_jaxpr(_apply)(jnp.zeros((m, k), in_dtype),
                                        jnp.zeros((k, n), in_dtype))

    primitives: Counter = Counter()
    callbacks: Counter = Counter()
    costs = [0.0, 0.0]                         # [flops, bytes]
    _walk(closed.jaxpr, primitives, callbacks, costs, 1.0)

    digest = hashlib.sha1()
    digest.update(str(closed.jaxpr).encode())
    digest.update(repr([str(v.aval) for v in closed.jaxpr.invars]).encode())
    digest.update(repr([str(v.aval) for v in closed.jaxpr.outvars]).encode())

    diags: List[PlanDiagnostic] = []
    for name, count in sorted(callbacks.items()):
        diags.append(PlanDiagnostic(
            code="host-callback", severity=ERROR,
            message=f"apply traces {count} {name!r} host-callback "
                    "equation(s) — every execution round-trips to the host",
            location="plan.apply",
            hint="phase-2 code must be pure jnp; hoist the host work into "
                 "the planner (phase 1)"))

    est = getattr(plan, "estimate", None)
    est_flops = float(getattr(est, "flops", 0.0) or 0.0)
    if est_flops > 0 and costs[0] > 0:
        ratio = costs[0] / est_flops
        if ratio > 2.0 or ratio < 0.5:
            diags.append(PlanDiagnostic(
                code="traffic-disagreement", severity=WARNING,
                message=f"jaxpr counts {costs[0]:.3e} dot FLOPs but the "
                        f"phase-1 estimate priced {est_flops:.3e} "
                        f"({ratio:.2f}x)",
                location="plan.estimate",
                hint="the selector ranks dataflows off this estimate; "
                     "check memory/traffic.py pricing for this dataflow"))

    return TraceReport(jaxpr=closed, primitives=dict(primitives),
                       callbacks=tuple(sorted(callbacks)),
                       flops=costs[0], bytes=costs[1],
                       aval_hash=digest.hexdigest(),
                       diagnostics=tuple(diags))


@dataclasses.dataclass(frozen=True)
class IndexMapReport:
    """Static audit of one schedule kind's scalar-prefetch index maps."""

    kind: str
    w_total: int
    n_runs: int
    aval_hashes: Dict[str, str]        # operand name -> stable trace hash
    diagnostics: Tuple[PlanDiagnostic, ...]

    @property
    def clean(self) -> bool:
        return not self.diagnostics


def _jaxpr_hash(closed) -> str:
    digest = hashlib.sha1()
    digest.update(str(closed.jaxpr).encode())
    digest.update(repr([str(v.aval) for v in closed.jaxpr.invars]).encode())
    digest.update(repr([str(getattr(v, "aval", v))
                        for v in closed.jaxpr.outvars]).encode())
    return digest.hexdigest()


@functools.lru_cache(maxsize=128)
def index_map_report(kind: str, w_total: int,
                     n_runs: int = 0) -> IndexMapReport:
    """Audit the fused kernels' index maps for one schedule.

    The streaming kernels address their operand blocks through the index
    maps exported as :data:`repro.kernels.stream.INDEX_MAPS`: the panel
    kernel's ``BlockSpec`` maps over its grid steps, and the block-run
    kernel's slot reads, which take a work entry and name the A block, the
    B block and the ``runs`` row its own DMAs copy.  A hazard there is a
    *compile-* or *DMA-time* failure class the plan verifier cannot see
    from the schedule arrays alone.  Each map is traced abstractly over the
    scalar-prefetch operands a (W=``w_total``) schedule provides and
    checked for:

    - **block-index shape** — the kernel's count of block coordinates
      (one slot for the block-run kernel's whole-block copies, one per
      block axis for a ``BlockSpec``), every coordinate a scalar integer
      (a vector or float output would mis-slice the operand stream);
    - **purity** — no host-callback primitives inside the map (a callback
      per grid step would serialize the DMA pipeline through the host);
    - **retrace identity** — tracing twice hashes identically, so the
      map cannot leak trace-dependent state into the grid (the
      ``pallas_call`` would silently recompile per apply);
    - **chunk cover** (block-run kernel) — its grid steps of
      ``run_chunk(W)`` entries each reach every entry below W, and no
      step is empty.

    Results are cached per (kind, W, R) — the checker calls this once per
    distinct schedule shape, not per plan.
    """
    from ..kernels.stream import INDEX_MAPS, run_chunk, run_grid_steps

    num_prefetch, n_coords, maps = INDEX_MAPS[kind]
    if w_total == 0:
        return IndexMapReport(kind, 0, n_runs, {}, ())

    def _trace(fn):
        try:
            return jax.make_jaxpr(fn)(
                jax.ShapeDtypeStruct((), jnp.int32),
                *[jax.ShapeDtypeStruct((w_total,), jnp.int32)] * num_prefetch)
        except TypeError:
            # some jax versions want concrete arrays for make_jaxpr
            return jax.make_jaxpr(fn)(
                jnp.zeros((), jnp.int32),
                *[jnp.zeros((w_total,), jnp.int32)] * num_prefetch)

    diags: List[PlanDiagnostic] = []
    if kind == "dest":
        chunk, steps = run_chunk(w_total), run_grid_steps(w_total)
        if steps * chunk < w_total or (steps - 1) * chunk >= w_total:
            diags.append(PlanDiagnostic(
                code="schedule-index-map", severity=ERROR,
                message=f"{steps} grid step(s) of {chunk} entries do not "
                        f"cover W={w_total} exactly once without an empty "
                        "step", location="run_chunk/run_grid_steps"))
    hashes: Dict[str, str] = {}
    for name, fn in maps.items():
        closed = _trace(fn)
        hashes[name] = _jaxpr_hash(closed)
        loc = f"INDEX_MAPS[{kind!r}][{name!r}]"
        outs = closed.jaxpr.outvars
        bad = [v for v in outs
               if getattr(getattr(v, "aval", None), "shape", None) != ()
               or not jnp.issubdtype(getattr(v, "aval").dtype, jnp.integer)]
        if len(outs) != n_coords or bad:
            diags.append(PlanDiagnostic(
                code="schedule-index-map", severity=ERROR,
                message=f"index map returns {len(outs)} output(s) with "
                        f"{len(bad)} non-scalar-integer aval(s); the "
                        f"{kind!r} kernel addresses its 3-D block stacks by "
                        f"{n_coords} scalar block coordinate(s)",
                location=loc))
        prims: Counter = Counter()
        callbacks: Counter = Counter()
        _walk(closed.jaxpr, prims, callbacks, [0.0, 0.0], 1.0)
        if callbacks:
            diags.append(PlanDiagnostic(
                code="schedule-index-map", severity=ERROR,
                message=f"index map traces host callback(s) "
                        f"{sorted(callbacks)} — every grid step would "
                        "round-trip to the host", location=loc))
        if _jaxpr_hash(_trace(fn)) != hashes[name]:
            diags.append(PlanDiagnostic(
                code="schedule-index-map", severity=ERROR,
                message="index map does not trace reproducibly — the "
                        "fused kernel would silently retrace per apply",
                location=loc))
    return IndexMapReport(kind, w_total, n_runs, hashes, tuple(diags))


@dataclasses.dataclass(frozen=True)
class Observation:
    """One :class:`RetraceDetector` observation of a plan."""

    key: Tuple[str, str, str]          # (fingerprint, backend, dataflow)
    aval_hash: str
    retraced: bool                     # hash changed vs the prior observation


class RetraceDetector:
    """Proves plan reuse never re-traces.

    Feed it every plan handed out by a :class:`repro.api.PlanCache`; two
    observations of the same (fingerprint, backend, dataflow) with
    different aval hashes mean the cached plan's traced program changed
    under reuse — the silent-retrace bug class PR 5 fixed in ServeEngine.
    """

    def __init__(self) -> None:
        self._seen: Dict[Tuple[str, str, str], str] = {}
        self.retraces: List[Observation] = []

    def observe(self, plan: Any, out_dtype=jnp.float32) -> Observation:
        key = (plan.fingerprint, plan.backend, plan.dataflow)
        aval_hash = trace_report(plan, out_dtype=out_dtype).aval_hash
        prev = self._seen.get(key)
        obs = Observation(key=key, aval_hash=aval_hash,
                          retraced=prev is not None and prev != aval_hash)
        self._seen[key] = aval_hash
        if obs.retraced:
            self.retraces.append(obs)
        return obs

    @property
    def stable(self) -> bool:
        return not self.retraces
