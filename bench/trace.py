"""Reduce a profiler trace (``.xplane.pb``) to named device intervals and
the numbers the per-layer metrics read.

Only ``jax.profiler.ProfileData`` is used to read the file.  The reduction
works on plain :class:`Event` tuples, so it is tested on synthetic traces.

- Device operations are the events on the ``XLA Ops`` line of every
  ``/device:TPU:<n>`` plane.
- The window is the benchmark's own host annotation ``bench.window``;
  ``bench.issue`` / ``bench.wait`` say what the host was doing, and label
  the device's idle gaps.
- An operation's event name is its HLO text (``%name = shape opcode(...)``).
  It is a kernel when it is a ``custom-call`` whose instruction name, less
  its ``.N`` suffix, is one of the configuration's kernel names, and a
  collective when its opcode is one; everything else on the device is glue.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW = "bench.window"
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "all-to-all")
_OPCODE = re.compile(r"(?:^|[\s}])([a-z][a-z0-9_-]*)\(")


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def parse_op(text: str) -> Tuple[str, str, str]:
    """(instruction name, opcode, result shape) of one HLO op's text."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text.lstrip("%"), "", ""
    m = _OPCODE.search(rest)
    if m is None:
        return name.lstrip("%"), "", rest
    return name.lstrip("%"), m.group(1), rest[:m.start(1)].strip()


def _base(name: str) -> str:
    return re.sub(r"\.\d+$", "", name)


def short_name(text: str) -> str:
    """``name opcode shape`` without the layout, for the breakdown."""
    name, opcode, shape = parse_op(text)
    return " ".join(p for p in (name, opcode, re.sub(r"\{[^}]*\}", "",
                                                         shape)) if p)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_events(path: str) -> List[Event]:
    """Device op/module events and the benchmark's host annotations."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out: List[Event] = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_PREFIX):
                    continue
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                   float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(ev: Event, w0: float, w1: float) -> Optional[Tuple[float, float]]:
    s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
    return (s, e) if e > s else None


def reduce_trace(events: Sequence[Event], *, kernel_names: Sequence[str],
                 top: int = 10) -> dict:
    """Reduce one traced window to seconds per category and device.

    Returns ``window_s``; ``devices``; per device averages ``busy_s`` and
    ``idle_share``; totals over all devices of ``kernel_s``,
    ``collective_s`` and ``glue_s`` (each op's time inside the window);
    ``kernel_events``; ``module_ms`` (durations of whole programs on device
    0, for per-call tails); and ``device_ops`` / ``idle_gaps`` as
    ``[[name, seconds], ...]``, at most ``top`` each.
    """
    windows = [e for e in events if e.name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    win = max(windows, key=lambda e: e.dur_ns)
    w0, w1 = win.start_ns, win.end_ns
    host = [e for e in events if e.name.startswith(HOST_PREFIX)
            and e.name != WINDOW and not e.plane.startswith(
                DEVICE_PLANE_PREFIX)]
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    for e in events:
        if not e.plane.startswith(DEVICE_PLANE_PREFIX):
            continue
        if e.line == OPS_LINE:
            ops.setdefault(e.plane, []).append(e)
        elif e.line == MODULES_LINE:
            modules.setdefault(e.plane, []).append(e)
    planes = sorted(ops, key=lambda p: (len(p), p))
    if not planes:
        raise ValueError("no device operations in the trace")

    kernel_s = collective_s = glue_s = 0.0
    kernel_events = 0
    by_name: Dict[str, float] = {}
    busy = []
    for p in planes:
        spans = []
        for e in ops[p]:
            c = _clip(e, w0, w1)
            if c is None:
                continue
            spans.append(c)
            d = (c[1] - c[0]) * 1e-9
            short = short_name(e.name)
            by_name[short] = by_name.get(short, 0.0) + d
            name, opcode, _ = parse_op(e.name)
            if opcode == "custom-call" and _base(name) in kernel_names:
                kernel_s += d
                kernel_events += 1
            elif any(opcode.startswith(k) for k in COLLECTIVES):
                collective_s += d
            else:
                glue_s += d
        busy.append(_union(spans))
    window_s = (w1 - w0) * 1e-9
    busy_s = sum(sum(e - s for s, e in u) for u in busy) * 1e-9 / len(planes)

    # idle gaps on the first device, labelled by the host annotation that
    # covers the gap's middle (the annotations run one after another)
    host.sort(key=lambda h: h.start_ns)
    starts = [h.start_ns for h in host]
    gaps = []
    edges = [w0] + [t for s, e in busy[0] for t in (s, e)] + [w1]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) / 2
        i = bisect.bisect_right(starts, mid) - 1
        label = "other"
        if i >= 0 and host[i].end_ns >= mid:
            label = host[i].name[len(HOST_PREFIX):]
        gaps.append([label, (e - s) * 1e-9])
    gaps.sort(key=lambda g: -g[1])

    mods = [e for e in modules.get(planes[0], [])
            if w0 <= e.start_ns and e.end_ns <= w1]
    return {
        "window_s": window_s,
        "devices": len(planes),
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "kernel_s": kernel_s,
        "kernel_events": kernel_events,
        "collective_s": collective_s,
        "glue_s": glue_s,
        "module_ms": [e.dur_ns * 1e-6 for e in mods],
        "device_ops": sorted(([n, s] for n, s in by_name.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": gaps[:top],
    }
