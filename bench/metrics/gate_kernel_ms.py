"""gate_kernel_ms: device time per call in the Pallas kernels of the gate
matmul (named scope ``ffn.gate``), summed over the chips, from the profiler
trace (``bench/scopes.py``)."""

from bench.scopes import kernel_ms_in


def read(rec):
    return kernel_ms_in(rec, "ffn.gate")
