"""Device time of the MoE layer's named scopes in a traced window.

    python3 bench/moe_scopes.py <workload>    # the last traced run's readings

``repro.models.sparse_moe`` runs each MoE layer's parts under the named
scopes ``moe.route``, ``moe.dispatch``, ``moe.experts``, ``moe.combine``
and ``moe.shared``.  Every device operation's ``op_name`` metadata keeps
its scope (``bench/scopes.py`` reads it from the trace's wire format; a
fused operation keeps the name of the operation the fusion ends in).  This
module sums, inside the window ``bench/run.py`` annotates, each scope's
device seconds over all its operations and over its kernels alone (a
kernel as ``bench/trace.py`` counts one).  A metric reader finds the trace
of the record it is given through :func:`for_record`; a trace without
these scopes reads as empty, never as an error.
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    # run as a script: bench/ itself must not shadow the stdlib's trace
    sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench.scopes import (SCOPE_STAT, TRACES, ScopedEvent,  # noqa: E402
                          kernel_names_of, op_metadata)
from bench.trace import (DEVICE_PLANE_PREFIX, OPS_LINE, WINDOW,  # noqa: E402
                         _base, _clip, find_xplane, parse_op)

MOE_SCOPE = re.compile(r"(?:^|/)(moe\.\w+)(?=/|$)")


def moe_scope_of(op_name: str) -> str:
    """The outermost ``moe.*`` component of an ``op_name`` path, or ""."""
    found = MOE_SCOPE.findall(op_name)
    return found[0] if found else ""


def load_events(path: str) -> List[ScopedEvent]:
    """The window's annotation and every device operation, with its
    ``moe.*`` scope."""
    from jax.profiler import ProfileData

    meta = op_metadata(path)
    out: List[ScopedEvent] = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device:
                    stats = meta.get((plane.name, ev.name), {})
                    scope = moe_scope_of(stats.get(SCOPE_STAT, ""))
                elif ev.name == WINDOW:
                    scope = ""
                else:
                    continue
                out.append(ScopedEvent(plane.name, line.name, ev.name,
                                       float(ev.start_ns),
                                       float(ev.duration_ns), scope))
    return out


def reduce_moe(events: Sequence[ScopedEvent], *,
               kernel_names: Sequence[str]) -> dict:
    """``window_s``; ``scope_s`` and ``kernel_s``: device seconds inside
    the window per ``moe.*`` scope, summed over devices, of all its
    operations and of its kernels."""
    windows = [e for e in events if e.name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    win = max(windows, key=lambda e: e.dur_ns)
    w0, w1 = win.start_ns, win.end_ns
    scope_s: Dict[str, float] = {}
    kernel_s: Dict[str, float] = {}
    for e in events:
        if not e.scope or not e.plane.startswith(DEVICE_PLANE_PREFIX):
            continue
        c = _clip(e, w0, w1)
        if c is None:
            continue
        d = (c[1] - c[0]) * 1e-9
        scope_s[e.scope] = scope_s.get(e.scope, 0.0) + d
        name, opcode, _ = parse_op(e.name)
        if opcode == "custom-call" and _base(name) in kernel_names:
            kernel_s[e.scope] = kernel_s.get(e.scope, 0.0) + d
    return {"window_s": (w1 - w0) * 1e-9, "scope_s": scope_s,
            "kernel_s": kernel_s}


_READ: Dict[Tuple[str, float], Optional[dict]] = {}


def for_record(rec: dict, traces: Path = TRACES) -> Optional[dict]:
    """The readings of the trace ``rec`` was reduced from, or None: the
    newest ``.xplane.pb`` under ``traces`` whose window is the record's,
    each file read once a process."""
    t = rec.get("trace")
    if not t:
        return None
    paths = glob.glob(str(traces / "*" / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        key = (path, os.path.getmtime(path))
        if key not in _READ:
            workload = Path(path).relative_to(traces).parts[0]
            try:
                _READ[key] = reduce_moe(
                    load_events(path), kernel_names=kernel_names_of(workload))
            except Exception:   # another cell's, or a broken, trace: a
                _READ[key] = None   # reader reports nothing, never raises
        got = _READ[key]
        if got and abs(got["window_s"] - t["window_s"]) <= \
                1e-9 * t["window_s"]:
            return got
    return None


def ms_per_call(rec: dict, scopes: Sequence[str], *,
                kernels: bool = False) -> Optional[float]:
    """Device ms per call in ``scopes`` (their kernels alone with
    ``kernels``), or None where the trace has none of them."""
    got = for_record(rec)
    table = got and got["kernel_s" if kernels else "scope_s"]
    if not table or not rec["calls"] or not any(s in table for s in scopes):
        return None
    return sum(table.get(s, 0.0) for s in scopes) / rec["calls"] * 1e3


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    path = find_xplane(str(TRACES / args[0]))
    print(json.dumps(reduce_moe(load_events(path),
                                kernel_names=kernel_names_of(args[0])),
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
