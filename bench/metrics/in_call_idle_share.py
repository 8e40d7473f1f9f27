"""in_call_idle_share: the first chip's idle time inside its calls (each
XLA program's interval less the union of its operations, from the profiler
trace, ``bench/scopes.py``) over the traced window, in percent.  Idle
between calls is ``device_idle_share`` less this."""

from bench import scopes


def read(rec):
    s = scopes.for_record(rec)
    if not s or s["window_s"] <= 0:
        return None
    return 100.0 * s["in_call_idle_s"] / s["window_s"]
