"""Shared substrate for the Flexagon Pallas kernels — the MRN analogue.

The paper's key hardware idea is *one* tree that both reduces (IP) and merges
(OP/Gust).  On TPU the analogue is one kernel substrate: every dataflow uses
the same VMEM accumulator discipline ("accumulate while the output coordinate
is unchanged, flush when it moves on"), the same scalar-prefetched coordinate
streams, and the same MXU block-GEMM inner op.  The three dataflow kernels
differ only in their grid/BlockSpec schedules — reduction and merging are two
configurations of this substrate, not two hardware stacks.

On the CPU the kernels run in Pallas interpret mode for validation; on a TPU
the same code compiles natively (BlockSpecs are MXU-aligned when the caller
uses 128-multiple blocks).  ``repro.config.resolve_interpret`` picks the
mode from the platform.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "accumulate_or_flush",
    "compiler_params",
    "grid_spec",
    "DEFAULT_BLOCK",
]

DEFAULT_BLOCK = (128, 128, 128)  # (bm, bk, bn) — MXU-aligned


def compiler_params(dimension_semantics: tuple[str, ...],
                    vmem_limit_bytes: int | None = None):
    """TPU compiler params (grid dimension semantics, and the VMEM the
    kernel may use when it needs more than the default); ignored when the
    kernel is interpreted."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=vmem_limit_bytes)


def grid_spec(num_scalar_prefetch: int, grid, in_specs, out_specs,
              scratch_shapes=()):
    """PrefetchScalarGridSpec wrapper (scalar operands feed the index maps —
    the TPU analogue of the paper's tile reader/filler address generators)."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=list(scratch_shapes),
    )


def accumulate_or_flush(acc_ref, out_ref, value, *, is_first, is_last,
                        out_dtype=None):
    """The MRN node discipline, lifted to block granularity.

    - ``is_first``: the output coordinate changed → reset the accumulator
      (a new fiber starts at the tree leaves).
    - accumulate ``value`` (coordinate match → adder mode).
    - ``is_last``: the fiber is complete → flush the full sum downstream
      (root emits; on TPU: write the VMEM accumulator back to HBM).
    """

    @pl.when(is_first)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += value

    @pl.when(is_last)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)
