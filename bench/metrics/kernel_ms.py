"""kernel_ms: device time per call in the Pallas kernels, summed over the
chips, from the profiler trace."""


def read(rec):
    t = rec.get("trace")
    if not t or not t["kernel_events"] or not rec["calls"]:
        return None
    return t["kernel_s"] / rec["calls"] * 1e3
