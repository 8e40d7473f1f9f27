#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration file, ``bench/traffic/<traffic>.json``,
``bench/limits/<workload>.json``, ``bench/systems/<system>.py`` (named by the
configuration) and one reader ``bench/metrics/<metric>.py`` per metric.
With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  There is no CPU fallback: without enough TPU chips the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ".bench_trace"      # under the checkout, one directory a cell
# the TPU runtime's own logs stay in the checkout, not in /tmp
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / TRACE_DIR / "tpu_logs"))
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path.pop(0)            # bench/trace.py must not shadow the stdlib
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

SAMPLE = 8                     # answers compared per run
COMPILE_CACHE = ROOT / ".jax_cache"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class Refused(Exception):
    """The run cannot report: wrong machine or incomplete checkout."""


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` and every file it names, found by name."""
    spec_path = root / "BENCHMARK.json"
    if not spec_path.is_file():
        raise Refused(f"no BENCHMARK.json in {root}")
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    bench = root / "bench"

    def wants(metric: dict) -> bool:
        return name in metric.get("workloads", cells)

    return {
        "cell": cell,
        "config": json.loads((root / cfg_entry["file"]).read_text()),
        "traffic": json.loads(
            (bench / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads(
            (bench / "limits" / f"{name}.json").read_text()),
        "end_to_end": [m for m in spec["end_to_end"] if wants(m)],
        "per_layer": [m for m in spec["per_layer"] if wants(m)],
    }


def reader(metric: str):
    return importlib.import_module(f"bench.metrics.{metric}").read


def system(name: str):
    return importlib.import_module(f"bench.systems.{name}")


def tpu_devices(chips: int):
    """The chips this cell asks for; refuses a CPU or too few chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX's default device is "
                      f"{devs[0].platform!r}; there is no CPU fallback")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX finds "
                      f"{len(devs)}")
    return devs[:chips]


def use_compile_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@contextlib.contextmanager
def _counting_compiles():
    """Counts tracing and compile events while the block runs."""
    import jax

    count = [0]

    def on(event, duration, **_):
        if event in COMPILE_EVENTS:
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        yield count
    finally:
        jax.monitoring.unregister_event_duration_listener(on)


def _window(layer, seconds: float, sample, traced: bool) -> dict:
    """Closed loop, one call outstanding, inputs cycled through the pool."""
    import jax

    pool = layer.pool
    n = len(pool)
    keep = set(sample)
    answers = {}
    ann = jax.profiler.TraceAnnotation
    issue_s, call_s = [], []
    t0 = t1 = time.perf_counter()
    with ann("bench.window") if traced else contextlib.nullcontext():
        while True:
            x = pool[len(call_s) % n]
            if traced:
                with ann("bench.issue"):
                    ti = time.perf_counter()
                    y = layer.call(x)
                    td = time.perf_counter()
                with ann("bench.wait"):
                    y.block_until_ready()
            else:
                ti = time.perf_counter()
                y = layer.call(x)
                td = time.perf_counter()
                y.block_until_ready()
            t_prev, t1 = t1, time.perf_counter()
            issue_s.append(td - ti)
            call_s.append(t1 - t_prev)
            slot = (len(call_s) - 1) % n
            if slot in keep:
                answers[slot] = y
            if t1 - t0 >= seconds:
                break
    return {"calls": len(call_s), "window_s": t1 - t0,
            "dispatch_s": sum(issue_s), "answers": answers,
            **_call_stats(call_s, issue_s)}


def _call_stats(call_s, issue_s) -> dict:
    """The median call, and the five slowest as [seconds into the window,
    call ms, issue ms]: where the host stalled, for reading a far-off run."""
    ends = list(itertools.accumulate(call_s))
    slow = sorted(sorted(range(len(call_s)), key=lambda i: -call_s[i])[:5])
    return {"call_ms_p50": 1e3 * statistics.median(call_s),
            "slowest_calls": [[ends[i], 1e3 * call_s[i], 1e3 * issue_s[i]]
                              for i in slow]}


def run_cell(name: str, seed: int, seconds: float, trace: bool, devices,
             *, root: Path = ROOT, t_start: float = T_START,
             control: bool = False) -> dict:
    """Set up, warm up, measure, check; returns the result object."""
    import jax

    from bench import inputs
    from bench.work import peaks_for

    found = load_cell(name, root)
    cfg, traffic, limits = found["config"], found["traffic"], found["limits"]
    kind = devices[0].device_kind
    peaks = peaks_for(kind) if devices[0].platform == "tpu" else None
    sysmod = system(cfg["system"])

    layer = sysmod.build(cfg, traffic, seed, devices)
    for x in layer.pool[: traffic["warmup_calls"]]:
        layer.call(x).block_until_ready()
    gc.collect()
    gc.freeze()        # set-up's objects are not the window's garbage
    setup_s = time.perf_counter() - t_start

    log_dir = None
    if trace:
        log_dir = str(root / TRACE_DIR / name)
        shutil.rmtree(log_dir, ignore_errors=True)
        jax.profiler.start_trace(log_dir)
    sample = inputs.sample_slots(traffic, seed, SAMPLE)
    with _counting_compiles() as count:
        win = _window(layer, seconds, sample, bool(trace))
    compiles = count[0]
    gc.unfreeze()
    if trace:
        jax.profiler.stop_trace()
    for y in win["answers"].values():
        y.block_until_ready()

    mem = [d.memory_stats() or {} for d in devices]
    peak_mem = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    xs = {s: layer.pool[s] for s in win["answers"]}
    record = {
        "setup_s": setup_s, "plan_build_s": layer.plan_build_s,
        "calls": win["calls"], "window_s": win["window_s"],
        "tokens": win["calls"] * layer.tokens_per_call,
        "dispatch_s": win["dispatch_s"], "work": layer.work,
        "peaks": peaks, "chips": len(devices), "trace": None,
    }
    info = dict(layer.info)
    layer.release()
    del layer

    if trace:
        from bench import trace as tr

        record["trace"] = tr.reduce_trace(
            tr.load_events(tr.find_xplane(log_dir)),
            kernel_names=cfg["kernel_names"])

    t_check = time.perf_counter()
    readings = sysmod.check(cfg, seed, devices[0].platform, win["answers"],
                            xs, control=control)
    check_s = time.perf_counter() - t_check
    missing = len(sample) - len(win["answers"])
    checks = {"out_gap": {"value": readings["out_gap"],
                          "limit": limits["out_gap"]},
              "answers_missing": {"value": missing, "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    failed = missing + sum(g > limits["out_gap"] for g in readings["gaps"])

    kind_metrics = found["per_layer"] if trace else found["end_to_end"]
    metrics = {}
    for m in kind_metrics:
        v = reader(m["name"])(record)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak_mem}
    out = {"correct": correct, "attempted": win["calls"],
           "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        t = record["trace"]
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        out["breakdown"] = {"device_ops": t["device_ops"],
                            "idle_gaps": t["idle_gaps"]}
    if peaks:
        info["roofline_bound"] = record["work"].least_time_s(
            peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])[1]
    out["info"] = dict(info, seed=seed, calls=win["calls"],
                       window_s=win["window_s"], call_ms_p50=win["call_ms_p50"],
                       slowest_calls=win["slowest_calls"],
                       start_to_window_s=setup_s, check_s=check_s,
                       compiles_in_window=compiles, **readings)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)["cell"]
        use_compile_cache()
        devices = tpu_devices(int(cell["chips"]))
    except (Refused, OSError, KeyError, ValueError) as e:
        print(f"[bench] refused: {e}", file=sys.stderr)
        return 2
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   devices)
    info = out["info"]
    print(f"[bench] {args.workload} seed={args.seed} calls={info['calls']} "
          f"window_s={info['window_s']!r} compiles_in_window="
          f"{info['compiles_in_window']}", file=sys.stderr)
    if info["compiles_in_window"]:
        print(f"[bench] WARNING: {info['compiles_in_window']} compile "
              "event(s) inside the measured window", file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
