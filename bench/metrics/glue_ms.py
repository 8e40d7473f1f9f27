"""glue_ms: device time per call in operations that are neither the Pallas
kernels nor collectives (packing, scatter of runs, SiLU*mul), summed over
the chips, from the profiler trace."""


def read(rec):
    t = rec.get("trace")
    if not t or not rec["calls"]:
        return None
    return t["glue_s"] / rec["calls"] * 1e3
