"""weight_pack_s: the program's own time in set-up moving the weights: the
masked dense weights to the host (``ffn.mask_s``) and each packed weight
back to the device (``ffn.pack_s``), both histograms that
``compress_ffn`` fills."""

from repro import obs


def read(rec):
    reg = obs.get_registry()
    mask, pack = reg.get("ffn.mask_s"), reg.get("ffn.pack_s")
    if mask is None or pack is None or not mask.count or not pack.count:
        return None
    return mask.sum + pack.sum
