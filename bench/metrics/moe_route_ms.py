"""moe_route_ms: device time per call in the MoE layers' router (named
scope ``moe.route``: the router matmul, sigmoid, group and expert top-k,
gates), summed over the MoE layers and chips, from the profiler trace
(``bench/moe_scopes.py``)."""

from bench.moe_scopes import ms_per_call


def read(rec):
    return ms_per_call(rec, ["moe.route"])
