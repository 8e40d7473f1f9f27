"""Process-wide execution knobs.

One place for defaults that used to be scattered per-function keywords.

Pallas interpret mode — the default for every kernel entry point
(``stream_spmm``/``stream_panel_spmm``, the ``ip/op/gust_spmm`` wrappers,
``moe_gmm.gmm``) and for plans executed through the ``pallas`` backend
follows the platform: kernels are interpreted only when JAX's default
backend is the CPU, and compile natively everywhere else.  An explicit
``interpret=`` argument at any call site still wins.

``use_compile_cache`` — JAX's persistent compilation cache for the
launchers and ``chip_smoke.py``.  ``JAX_COMPILATION_CACHE_DIR``, when set,
is read by JAX itself and left alone; otherwise the cache lives at the
fixed in-checkout path :data:`COMPILE_CACHE_DIR` (ignored by git).

``REPRO_VERIFY`` — pre-execution plan verification default (see
``repro.analysis.verify_plan``).  Unset or falsy, plans are handed out
unchecked (production default: verification re-derives every invariant on
the host, which is wasted work on a trusted path); set ``REPRO_VERIFY=1``
to gate every ``flexagon_plan``/``PlanCache`` build behind the verifier —
the test suite turns this on in ``tests/conftest.py``.  An explicit
``verify=`` argument at any call site still wins.

``virtual_devices`` — the one place that sets
``--xla_force_host_platform_device_count`` (virtual CPU devices for mesh /
``shard_map`` work without TPUs).  Launchers (``launch.dryrun`` /
``launch.roofline``), the test session, and examples all route through it
instead of hand-writing ``XLA_FLAGS``.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["COMPILE_CACHE_DIR", "interpret_default", "resolve_interpret",
           "use_compile_cache", "verify_default", "resolve_verify",
           "virtual_devices"]

#: fixed persistent-compile-cache path inside the checkout: the path is part
#: of the cache key, so it never derives from a temp name, pid or the time
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_DEVICE_FLAG = "--xla_force_host_platform_device_count"


def virtual_devices(n: int = 8, *, override: bool = False) -> str:
    """Request ``n`` host-platform (CPU) devices via ``XLA_FLAGS``.

    Must run before jax initializes its backend (jax locks the device count
    on first device query, *not* on import — so calling this right after
    ``import repro`` is still in time).  Preserves any other flags already
    in ``XLA_FLAGS``; an existing device-count flag is kept unless
    ``override=True``.  Returns the resulting ``XLA_FLAGS`` value.
    """
    flag = f"{_DEVICE_FLAG}={int(n)}"
    parts = os.environ.get("XLA_FLAGS", "").split()
    if any(p.startswith(_DEVICE_FLAG) for p in parts):
        if override:
            parts = [p for p in parts if not p.startswith(_DEVICE_FLAG)]
            parts.append(flag)
    else:
        parts.append(flag)
    os.environ["XLA_FLAGS"] = " ".join(parts)
    return os.environ["XLA_FLAGS"]

_TRUE = {"1", "true", "yes", "on"}


def interpret_default() -> bool:
    """Pallas interpret-mode default: on exactly when the default backend
    is the CPU (read at call time, so it sees the backend jax settled on)."""
    import jax

    return jax.default_backend() == "cpu"


def resolve_interpret(explicit: bool | None = None) -> bool:
    """An explicit per-call value wins; ``None`` follows the platform."""
    return interpret_default() if explicit is None else bool(explicit)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and this
    sets nothing.  Otherwise the cache goes to :data:`COMPILE_CACHE_DIR`.
    Called by the launchers and ``chip_smoke.py``, never by the tests.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def verify_default() -> bool:
    """Global plan-verification default (``REPRO_VERIFY``).

    Read at call time, not import time, so tests can set it late.
    Off unless explicitly enabled — verification is a debugging/CI gate,
    not a production tax.
    """
    return os.environ.get("REPRO_VERIFY", "").strip().lower() in _TRUE


def resolve_verify(explicit: bool | None = None) -> bool:
    """An explicit per-call value wins; ``None`` defers to the global knob."""
    return verify_default() if explicit is None else bool(explicit)
