"""Grouped matmul (MegaBlocks-style) Pallas kernel for MoE expert compute.

MoE dispatch *is* SpMSpM: the token→expert routing matrix is sparse and the
expert weights are dense-per-expert.  After the Gustavson-style sort (tokens
grouped by expert — the leader fiber), expert compute becomes a block-diagonal
sparse matmul: each M tile multiplies only its group's weight slab.  This
kernel is the framework's production deployment of the paper's Gust dataflow
(see DESIGN.md §5): group boundaries are padded to the M tile (as MegaBlocks
pads to the block size) and the per-tile group id is scalar-prefetched.

x: (M, K) rows sorted by group, group boundaries multiples of ``bm``.
w: (G, K, N) per-group weights.
group_ids: (M / bm,) group of each row tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..config import resolve_interpret
from .common import accumulate_or_flush, compiler_params, grid_spec

__all__ = ["gmm", "pad_groups"]


def _kernel(gid_ref, x_ref, w_ref, o_ref, acc_ref, *, kt: int):
    k = pl.program_id(2)
    accumulate_or_flush(
        acc_ref, o_ref,
        jnp.dot(x_ref[...], w_ref[0], preferred_element_type=jnp.float32),
        is_first=k == 0,
        is_last=k == kt - 1,
    )


def gmm(x: jax.Array, w: jax.Array, group_ids: jax.Array, *,
        bm: int = 128, bk: int = 128, bn: int = 128,
        out_dtype=None, interpret: bool | None = None) -> jax.Array:
    """Grouped matmul: out[t*bm:(t+1)*bm] = x[t*bm:(t+1)*bm] @ w[group_ids[t]].

    Requires M % bm == K % bk == N % bn == 0 (callers pad; see
    :func:`pad_groups`).  ``interpret=None`` follows the platform
    (CPU → interpret).
    """
    interpret = resolve_interpret(interpret)
    m, kdim = x.shape
    g, kdim2, n = w.shape
    assert kdim == kdim2, (x.shape, w.shape)
    assert m % bm == 0 and kdim % bk == 0 and n % bn == 0, (m, kdim, n)
    mt, kt, nt = m // bm, kdim // bk, n // bn
    assert group_ids.shape == (mt,), (group_ids.shape, mt)
    out_dtype = out_dtype or x.dtype

    spec = grid_spec(
        num_scalar_prefetch=1,
        grid=(mt, nt, kt),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda t, j, k, gid: (t, k)),
            pl.BlockSpec((1, bk, bn), lambda t, j, k, gid: (gid[t], k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda t, j, k, gid: (t, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, kt=kt),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(group_ids, jnp.int32), x, w)


def pad_groups(group_sizes: np.ndarray, bm: int):
    """Round each group up to a multiple of ``bm``.

    Returns (padded_sizes, row_tile_group_ids, scatter_index) where
    ``scatter_index[i]`` is the padded-row position of original row *i*.
    """
    group_sizes = np.asarray(group_sizes)
    padded = ((group_sizes + bm - 1) // bm) * bm
    padded = np.maximum(padded, 0)
    tile_counts = padded // bm
    gids = np.repeat(np.arange(len(group_sizes)), tile_counts).astype(np.int32)
    starts = np.concatenate([[0], np.cumsum(padded)[:-1]])
    orig_starts = np.concatenate([[0], np.cumsum(group_sizes)[:-1]])
    scatter = np.concatenate([
        starts[g] + np.arange(group_sizes[g]) for g in range(len(group_sizes))
    ]) if group_sizes.sum() else np.zeros(0, np.int64)
    del orig_starts
    return padded, gids, scatter.astype(np.int32)
