"""moe_dispatch_ms: device time per call in grouping the assignments by
held expert and gathering their rows into slots (``moe.dispatch``) and in
the gate-weighted combine (``moe.combine``), summed over the MoE layers
and chips, from the profiler trace (``bench/moe_scopes.py``)."""

from bench.moe_scopes import ms_per_call


def read(rec):
    return ms_per_call(rec, ["moe.dispatch", "moe.combine"])
