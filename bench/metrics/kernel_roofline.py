"""kernel_roofline: the least time the layer's work needs on one chip,
max(FLOPs / peak FLOP/s, bytes / peak bytes/s), over the device time the
Pallas kernels took for it (summed over chips), in percent.  The work is
counted in ``bench/work.py``; the bound that applies is in ``info``."""


def read(rec):
    t, peaks = rec.get("trace"), rec.get("peaks")
    if not t or not peaks or not t["kernel_events"] or t["kernel_s"] <= 0:
        return None
    least, _ = rec["work"].least_time_s(peaks["bf16_flops_per_s"],
                                        peaks["hbm_bytes_per_s"])
    return 100.0 * least * rec["calls"] / t["kernel_s"]
