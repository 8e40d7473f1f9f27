"""expert_kernel_ms: device time per call in the grouped kernels of the
held experts (the kernels under named scope ``moe.experts``: gate, up and
down of every MoE layer), summed over the chips, from the profiler trace
(``bench/moe_scopes.py``)."""

from bench.moe_scopes import ms_per_call


def read(rec):
    return ms_per_call(rec, ["moe.experts"], kernels=True)
