"""ffn_mfu: effectual FLOPs of the calls completed in the window, over the
window and the chips' bf16 peak, in percent (the whole step's share)."""


def read(rec):
    peaks = rec.get("peaks")
    if not peaks or rec["window_s"] <= 0:
        return None
    rate = rec["work"].flops * rec["calls"] / rec["window_s"]
    return 100.0 * rate / (rec["chips"] * peaks["bf16_flops_per_s"])
