"""kernel_ns_per_pair: device time in the Pallas kernels (as ``kernel_ms``)
over the (weight, activation) block pairs they multiplied: calls times the
program's own count per call, the ``ffn.block_pairs`` gauge that
``compress_ffn`` sets from its plans' stream schedules.  Pairs, not grid
steps: a kernel that folds several pairs into one step reads lower."""

from repro import obs


def read(rec):
    t = rec.get("trace")
    gauge = obs.get_registry().get("ffn.block_pairs")
    if not t or not t["kernel_events"] or not rec["calls"] or gauge is None \
            or gauge.value <= 0:
        return None
    return t["kernel_s"] / (rec["calls"] * gauge.value) * 1e9
