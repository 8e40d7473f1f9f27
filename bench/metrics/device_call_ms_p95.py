"""device_call_ms_p95: 95th percentile of one call's program time on the
first chip (the trace's XLA module events), over every call in the traced
window; needs at least 20 calls."""

import statistics


def read(rec):
    t = rec.get("trace")
    if not t or len(t["module_ms"]) < 20:
        return None
    return statistics.quantiles(t["module_ms"], n=20)[-1]
