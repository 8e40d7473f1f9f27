"""device_idle_share: 1 - (union of device operation intervals) / traced
window, averaged over the chips, in percent."""


def read(rec):
    t = rec.get("trace")
    if not t or t["idle_share"] is None:
        return None
    return 100.0 * t["idle_share"]
