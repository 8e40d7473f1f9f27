"""dispatch_ms: host time from issuing the jitted call to its return (the
enqueue, not the device work), mean per call over the window."""


def read(rec):
    return rec["dispatch_s"] / rec["calls"] * 1e3 if rec["calls"] else None
