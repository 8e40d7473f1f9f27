"""tokens_per_s: tokens through the layer over the whole window (host clock)."""


def read(rec):
    return rec["tokens"] / rec["window_s"] if rec["window_s"] > 0 else None
