#!/usr/bin/env python3
"""Readings of a traced window that rest on the program's own names.

    python3 bench/scopes.py <workload>     # the last traced run's readings

``bench/trace.py`` reduces the window's ``.xplane.pb`` to the accepted
per-layer numbers.  This module reads the same file again and keeps what
that reduction drops:

- each kernel's ``jax.named_scope`` (``ffn.gate``, ``ffn.up``,
  ``ffn.down``), from the stat of its ``XLA Ops`` event's metadata that
  carries the HLO ``op_name`` (``SCOPE_STAT``).  ``ProfileData`` shows an
  event's own stats but not its metadata's, so the metadata is read from
  the ``XSpace`` protobuf's wire format (:func:`op_metadata`);
- the ``XLA Modules`` events of the first device, to split its idle time
  into idle inside a call (between the ops of one program) and idle
  between calls;
- every host event, on any thread, to say what the host was doing in the
  longest idle gaps of the first device.

A kernel is what ``bench/trace.py`` counts as one (a ``custom-call`` whose
instruction name, less its ``.N`` suffix, is a kernel name), so the
seconds by scope sum to its ``kernel_s``.  A metric reader finds the trace
of the record it is given through :func:`for_record`.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import struct
import sys
from pathlib import Path
from typing import Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

if __package__ in (None, ""):
    # run as a script: bench/ itself must not shadow the stdlib's trace
    sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench.trace import (DEVICE_PLANE_PREFIX, MODULES_LINE, OPS_LINE,  # noqa: E402
                         WINDOW, _base, _clip, _union, find_xplane,
                         parse_op)

ROOT = Path(__file__).resolve().parents[1]
TRACES = ROOT / ".bench_trace"     # bench/run.py traces cell <name> here
SCOPE_STAT = "tf_op"               # holds the op's HLO op_name metadata
SCOPE = re.compile(r"(?:^|/)(ffn\.\w+)(?=/|$)")
LONG_GAPS = 5                      # gaps given a host account
HOST_PER_GAP = 8                   # host events kept per gap, longest first


class ScopedEvent(NamedTuple):
    """An :class:`bench.trace.Event` with its op's scope (a tuple: a
    window holds millions)."""

    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    scope: str = ""                # innermost ffn.* scope of a device op

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def scope_of(op_name: str) -> str:
    """The innermost ``ffn.*`` component of an ``op_name`` path, or ""."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else ""


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of one protobuf message: an int
    for a varint, a memoryview slice for anything else."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind in (1, 2, 5):
            n = 8 if kind == 1 else 4 if kind == 5 else None
            if n is None:
                n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_metadata(path: str) -> Dict[Tuple[str, str], Dict[str, str]]:
    """(device plane, event name) -> the stats of that event's metadata,
    as text, from the ``.xplane.pb`` at ``path``.

    ``XSpace.planes`` (1); ``XPlane`` name (2), ``event_metadata`` (4) and
    ``stat_metadata`` (5), both maps of (1: id, 2: value); an
    ``XEventMetadata``'s name (2), display name (4) and stats (5); an
    ``XStat``'s stat id (1) and value: double (2), uint64 (3), int64 (4),
    string (5), bytes (6) or a reference to a stat metadata's name (7).
    """
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out: Dict[Tuple[str, str], Dict[str, str]] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = _text(v)
            elif f in (4, 5) and name.startswith(DEVICE_PLANE_PREFIX):
                entry = dict(_fields(v))
                if f == 4:
                    events.append(entry.get(2, b""))
                else:
                    md = dict(_fields(entry.get(2, b"")))
                    stat_names[entry.get(1, 0)] = _text(md.get(2, b""))
        for md in events:
            names, stats = [], {}
            for f, v in _fields(md):
                if f in (2, 4):
                    names.append(_text(v))
                elif f == 5:
                    stat = dict(_fields(v))
                    value = next((stat[k] for k in (5, 7, 4, 3, 2, 6)
                                  if k in stat), "")
                    if 7 in stat:
                        value = stat_names.get(value, "")
                    elif 2 in stat:
                        value = struct.unpack("<d", value)[0]
                    elif isinstance(value, memoryview):
                        value = _text(value)
                    stats[stat_names.get(stat.get(1, 0), "")] = str(value)
            for n in names:
                if n:
                    out[(name, n)] = stats
    return out


def load_events(path: str) -> List[ScopedEvent]:
    """Device op/module events (custom-calls with their scope) and every
    host event of the window's host planes."""
    from jax.profiler import ProfileData

    meta = op_metadata(path)
    pd = ProfileData.from_file(path)
    out: List[ScopedEvent] = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name, scope = ev.name, ""
                if device and "custom-call(" in name:
                    stats = meta.get((plane.name, name), {})
                    scope = scope_of(stats.get(SCOPE_STAT, ""))
                out.append(ScopedEvent(plane.name, line.name, name,
                                       float(ev.start_ns),
                                       float(ev.duration_ns), scope))
    return out


def _covered(busy: Sequence[Tuple[float, float]], starts: Sequence[float],
             s: float, e: float) -> float:
    """Length of [s, e) covered by the sorted, disjoint ``busy`` spans."""
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    total = 0.0
    while i < len(busy) and busy[i][0] < e:
        total += max(0.0, min(e, busy[i][1]) - max(s, busy[i][0]))
        i += 1
    return total


def reduce_scopes(events: Sequence[ScopedEvent], *, kernel_names: Sequence[str],
                  long_gaps: int = LONG_GAPS,
                  per_gap: int = HOST_PER_GAP) -> dict:
    """Reduce one traced window to the readings the program's names allow.

    Returns ``window_s``; ``kernel_s_by_scope`` (kernel seconds inside the
    window per scope, summed over devices; "" holds unscoped kernels);
    ``in_call_idle_s`` (on the first device, the union of its ``XLA
    Modules`` intervals less the union of its ops, inside the window); and
    ``long_gap_host``: for each of the ``long_gaps`` longest idle gaps of
    the first device, ``{"at_s", "gap_ms", "host"}`` with ``host`` the
    host events overlapping the gap as ``[name, thread, overlap ms]``,
    summed by name and thread, longest first, at most ``per_gap``.
    """
    windows = [e for e in events if e.name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    win = max(windows, key=lambda e: e.dur_ns)
    w0, w1 = win.start_ns, win.end_ns
    ops: Dict[str, List[ScopedEvent]] = {}
    modules: List[ScopedEvent] = []
    host: List[ScopedEvent] = []
    for e in events:
        if not e.plane.startswith(DEVICE_PLANE_PREFIX):
            if e is not win:
                host.append(e)
        elif e.line == OPS_LINE:
            ops.setdefault(e.plane, []).append(e)
        elif e.line == MODULES_LINE:
            modules.append(e)
    planes = sorted(ops, key=lambda p: (len(p), p))
    if not planes:
        raise ValueError("no device operations in the trace")

    by_scope: Dict[str, float] = {}
    for p in planes:
        for e in ops[p]:
            c = _clip(e, w0, w1) if "custom-call(" in e.name else None
            if c is None:
                continue
            name, opcode, _ = parse_op(e.name)
            if opcode == "custom-call" and _base(name) in kernel_names:
                by_scope[e.scope] = by_scope.get(e.scope, 0.0) + \
                    (c[1] - c[0]) * 1e-9

    first = planes[0]
    busy = _union(c for c in (_clip(e, w0, w1) for e in ops[first]) if c)
    starts = [s for s, _ in busy]
    calls = _union(c for c in (_clip(e, w0, w1) for e in modules
                               if e.plane == first) if c)
    in_call_idle_ns = sum((e - s) - _covered(busy, starts, s, e)
                          for s, e in calls)

    edges = [w0] + [t for s, e in busy for t in (s, e)] + [w1]
    gaps = sorted(((s, e) for s, e in zip(edges[0::2], edges[1::2])
                   if e > s), key=lambda g: g[0] - g[1])[:long_gaps]
    h0 = np.array([h.start_ns for h in host], dtype=float)
    h1 = np.array([h.end_ns for h in host], dtype=float)
    accounts = []
    for s, e in sorted(gaps):
        seen: Dict[Tuple[str, str], float] = {}
        for i in np.flatnonzero((h0 < e) & (h1 > s)):
            h = host[i]
            key = (h.name, h.line)
            seen[key] = seen.get(key, 0.0) + float(min(e, h1[i]) -
                                                   max(s, h0[i]))
        top = sorted(seen.items(), key=lambda kv: -kv[1])[:per_gap]
        accounts.append({"at_s": (s - w0) * 1e-9, "gap_ms": (e - s) * 1e-6,
                         "host": [[n, t, ns * 1e-6] for (n, t), ns in top]})
    accounts.sort(key=lambda a: -a["gap_ms"])
    return {"window_s": (w1 - w0) * 1e-9,
            "kernel_s_by_scope": by_scope,
            "in_call_idle_s": in_call_idle_ns * 1e-9,
            "long_gap_host": accounts}


def kernel_names_of(workload: str) -> List[str]:
    """The kernel names of ``workload``'s configuration."""
    from bench.run import load_cell

    return load_cell(workload, ROOT)["config"]["kernel_names"]


_READ: Dict[Tuple[str, float], Optional[dict]] = {}


def for_record(rec: dict, traces: Path = TRACES) -> Optional[dict]:
    """The readings of the trace ``rec`` was reduced from, or None.

    ``bench/run.py`` writes each cell's trace under ``traces/<workload>``
    and reduces it into ``rec["trace"]``.  The newest ``.xplane.pb`` whose
    window is that record's is the one; each file is read once a process.
    """
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
                _READ[key] = reduce_scopes(
                    load_events(path), kernel_names=kernel_names_of(workload))
            except Exception:   # another cell's, or a broken, trace: a
                _READ[key] = None   # reader reports nothing, never raises
        got = _READ[key]
        if got and abs(got["window_s"] - t["window_s"]) <= \
                1e-9 * t["window_s"]:
            return got
    return None


def kernel_ms_in(rec: dict, scope: str) -> Optional[float]:
    """Kernel device time per call under ``scope``, in ms, or None."""
    s = for_record(rec)
    if not s or scope not in s["kernel_s_by_scope"] or not rec["calls"]:
        return None
    return s["kernel_s_by_scope"][scope] / rec["calls"] * 1e3


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    path = find_xplane(str(TRACES / args[0]))
    out = reduce_scopes(load_events(path),
                        kernel_names=kernel_names_of(args[0]))
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
