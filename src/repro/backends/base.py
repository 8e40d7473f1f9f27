"""The ``ExecutionBackend`` protocol and backend registry.

The paper's phase 1 is "estimate every dataflow's cost, pick one, configure
the hardware".  This module is the seam that keeps both halves swappable:

- an :class:`ExecutionBackend` is one *execution substrate* for planned
  SpMSpM — it declares what it can run (:class:`BackendCapability`), builds
  pattern-only auxiliary schedules at plan time (:meth:`ExecutionBackend.
  prepare` — the "configure the hardware" step), executes a plan
  jit-compatibly (:meth:`ExecutionBackend.execute`), and prices a
  (shape, dataflow) pair (:meth:`ExecutionBackend.cost` — the oracle that
  selection policies consult);
- the registry maps backend names to live instances so a
  :class:`repro.api.FlexagonPlan` can carry only a *name* (plans stay
  pytree-serializable) and resolve the substrate at execution time.

Three backends ship by default (registered in :mod:`repro.backends`):
``reference`` (pure-jnp dataflow executors), ``pallas`` (the TPU kernels),
and ``simulator`` (cycle-level cost oracle + reference-validated execution).
"""
from __future__ import annotations

import abc
import dataclasses
from typing import Any, Dict, Optional, Tuple, Union

import jax

from ..core.dataflows import DATAFLOWS
from ..core.formats import SparseFormat
from ..core.selector import LayerShape, TPUSpec, estimate

__all__ = [
    "TABLE3_FORMATS",
    "BackendCapability",
    "ExecutionBackend",
    "allowed_dataflows",
    "register_backend",
    "get_backend",
    "available_backends",
]

#: Table 3 operand formats per dataflow: (A format, B format).
TABLE3_FORMATS = {
    "ip_m": (SparseFormat.BCSR, SparseFormat.BCSC),
    "op_m": (SparseFormat.BCSC, SparseFormat.BCSR),
    "gust_m": (SparseFormat.BCSR, SparseFormat.BCSR),
    "ip_n": (SparseFormat.BCSR, SparseFormat.BCSC),
    "op_n": (SparseFormat.BCSC, SparseFormat.BCSR),
    "gust_n": (SparseFormat.BCSC, SparseFormat.BCSC),
}


@dataclasses.dataclass(frozen=True)
class BackendCapability:
    """What one backend can run — consulted during phase-1 negotiation.

    ``dataflows``      — dataflow names the backend executes.
    ``formats``        — (A, B) operand-format pairs it ingests.
    ``block_multiple`` — block dims must be multiples of this (1 = any; a
                         compiled TPU path would declare its MXU alignment).
    """

    dataflows: Tuple[str, ...]
    formats: Tuple[Tuple[SparseFormat, SparseFormat], ...]
    block_multiple: int = 1

    def supports(self, dataflow: str, fmt_a: SparseFormat,
                 fmt_b: SparseFormat,
                 block_shape: Tuple[int, int, int]) -> bool:
        if dataflow not in self.dataflows:
            return False
        if (fmt_a, fmt_b) not in self.formats:
            return False
        return all(b % self.block_multiple == 0 for b in block_shape)


class ExecutionBackend(abc.ABC):
    """One execution substrate behind the plan API (see module docstring).

    Subclasses must be stateless with respect to individual plans: every
    per-pattern artifact belongs in the aux dict returned by :meth:`prepare`
    and stored *on the plan*, so that plans survive pytree round trips and
    one backend instance serves any number of plans concurrently.
    """

    name: str = "abstract"

    #: Can :class:`repro.memory.TiledPlan` stream OP k-slabs through one
    #: ``jax.lax.scan`` on this backend?  Requires ``execute`` to accept
    #: *traced* plan leaves (index plans / layouts / aux schedules as
    #: scan-carried values): only array shapes may steer control flow or
    #: kernel grids.  Both ``reference`` and ``pallas`` qualify (the pallas
    #: kernels take a shape-uniform :class:`repro.kernels.StreamSchedule`);
    #: a backend whose phase 2 needs per-tile concrete host schedules
    #: leaves this ``False`` and gets the unrolled tile loop instead.
    scan_streaming: bool = False

    #: Can :class:`repro.dist.ShardedPlan` run this backend's ``execute``
    #: inside a ``shard_map`` shard and merge cross-shard partial sums with
    #: collectives (``jax.lax.psum``)?  Requires the same traced-plan-leaf
    #: tolerance as ``scan_streaming`` (each device slices its sub-plan out
    #: of a sharded stack); backends that need concrete host-side schedules
    #: leave this ``False`` and get the sequential shard loop instead.
    collective_merge: bool = False

    #: The ``plan.aux`` key under which this backend stores the
    #: :class:`repro.kernels.StreamSchedule` its ``execute`` consumes, or
    #: ``None`` for backends that execute straight off the index plan.
    #: This is the registration seam for the static schedule checker
    #: (DESIGN.md §19): when set, ``verify_plan`` requires the key to be
    #: present on every prepared plan and proves the five schedule
    #: invariant families over it — a new backend (or a new scheduler on
    #: an existing one) opts into checking by declaring its key here and
    #: keeping the artifact a ``StreamSchedule``.
    schedule_aux_key: Optional[str] = None

    @abc.abstractmethod
    def capabilities(self) -> BackendCapability:
        """Declare what this backend can run."""

    def supports(self, dataflow: str, fmt_a: SparseFormat,
                 fmt_b: SparseFormat,
                 block_shape: Tuple[int, int, int]) -> bool:
        return self.capabilities().supports(dataflow, fmt_a, fmt_b,
                                            block_shape)

    def prepare(self, plan) -> Dict[str, Any]:
        """Phase-1 auxiliary schedules for ``plan`` (pattern-only, host-side).

        Runs exactly once per plan, at plan time.  The returned dict rides on
        the plan (``plan.aux``) and is handed back to :meth:`execute`; it must
        depend only on the plan's sparsity *patterns*, never on values.
        """
        del plan
        return {}

    def kernel_attrs(self, plan) -> Dict[str, int]:
        """What one apply of the prepared ``plan`` runs on the device, as
        ``plan.prepare`` span attributes; empty for a backend that runs no
        work-list kernel."""
        del plan
        return {}

    def uniform_aux(self, plans) -> None:
        """Make sibling plans' aux schedules shape-uniform so they stack.

        Called (host-side, phase 1) on a group of prepared sub-plans that
        are about to be stacked into one slab/shard pytree axis
        (``TiledPlan`` scan lanes, ``ShardedPlan`` shard stacks).  A
        backend whose aux arrays are work-list sized overrides this to pad
        them to shared extents *in place* (mutating each ``plan.aux``);
        the default is a no-op for backends whose aux is already uniform
        (or empty, like ``reference``).
        """
        del plans

    @abc.abstractmethod
    def execute(self, plan, a, b, out_dtype) -> jax.Array:
        """Phase 2: run ``C = A @ B`` for compressed operands ``a``/``b``
        (BlockCSR/BlockCSC in the plan's Table 3 formats).

        Must be jit-compatible and must not rebuild any phase-1 artifact —
        ``repro.api.PHASE1_COUNTERS`` stays untouched (asserted by tests).
        """

    def cost(self, shape: LayerShape, dataflow: str,
             spec: Optional[TPUSpec] = None) -> float:
        """Estimated execution time in seconds for ``dataflow`` on ``shape``.

        The oracle that selection policies consult.  Default: the analytical
        roofline estimate; backends with better knowledge (cycle models,
        measurements) override.
        """
        return estimate(shape, dataflow, spec or TPUSpec()).time_s


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, ExecutionBackend] = {}


def register_backend(backend: ExecutionBackend, *,
                     overwrite: bool = False) -> ExecutionBackend:
    """Register ``backend`` under ``backend.name``.

    Registration makes plans built against the backend serializable: a plan
    stores only the name and re-resolves the instance at execution time.
    """
    if not overwrite and backend.name in _REGISTRY \
            and _REGISTRY[backend.name] is not backend:
        raise ValueError(f"backend {backend.name!r} already registered "
                         "(pass overwrite=True to replace)")
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(backend: Union[str, ExecutionBackend]) -> ExecutionBackend:
    """Resolve a backend name (or pass an instance through, registering it).

    A not-yet-registered instance is registered under its name so that plans
    built against it (which store the *name*) resolve back to it.  An
    instance whose name is already taken by a *different* instance is
    rejected — silently replacing the registered backend would re-target
    every existing plan that resolves that name; give the instance a unique
    ``name`` or call :func:`register_backend` with ``overwrite=True``
    deliberately.
    """
    if isinstance(backend, ExecutionBackend):
        existing = _REGISTRY.get(backend.name)
        if existing is None:
            register_backend(backend)
        elif existing is not backend:
            raise ValueError(
                f"a different backend is already registered as "
                f"{backend.name!r}; give your instance a unique .name or "
                "call register_backend(..., overwrite=True) explicitly")
        return backend
    try:
        return _REGISTRY[backend]
    except KeyError:
        raise KeyError(
            f"unknown backend {backend!r}; available: "
            f"{sorted(_REGISTRY)}") from None


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def allowed_dataflows(backend: ExecutionBackend,
                      block_shape: Tuple[int, int, int]) -> Tuple[str, ...]:
    """Capability negotiation: the dataflows ``backend`` admits at this block
    shape, with each dataflow's Table 3 operand formats.  The single source
    for both the plan path and the policy path."""
    return tuple(d for d in DATAFLOWS
                 if backend.supports(d, *TABLE3_FORMATS[d],
                                     tuple(block_shape)))
