"""moe_plan_s: the program's own time in set-up planning and packing the
held experts of every MoE layer, the sum of its ``moe.compress_s``
histogram (one observation per layer's ``compress_experts``)."""

from repro import obs


def read(rec):
    h = obs.get_registry().get("moe.compress_s")
    return h.sum if h is not None and h.count else None
