"""expert_kernel_roofline: the least time the routed experts' work of the
window's calls needs on one chip, each call's max(FLOPs / peak FLOP/s,
bytes / peak bytes/s) summed, over the device time of the kernels under
``moe.experts``, in percent.  The work (``bench/work_moe.py``) is counted
from the program's own per-call live rows: the live rows against their
experts' kept blocks, and the kept blocks of every expert with a row."""

from bench import moe_scopes


def read(rec):
    got, peaks = moe_scopes.for_record(rec), rec.get("peaks")
    work = rec.get("work")
    if not got or not peaks or not hasattr(work, "least_s"):
        return None
    kernel_s = got["kernel_s"].get("moe.experts", 0.0)
    if kernel_s <= 0:
        return None
    least = work.least_s(peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"],
                         calls=rec["calls"])
    return 100.0 * least / kernel_s
