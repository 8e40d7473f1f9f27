"""Plain reference of a block-pruned SwiGLU FFN layer, and its control.

    y = (silu(x @ Wg) * (x @ Wu)) @ Wd,   W = W_dense * (mask (x) 1_{b x b})

Straightforward ``jax.numpy`` in float32, no kernels, no plan, nothing of
the program imported: the weights come from ``inputs.make_weights`` with the
same seed, the mask is expanded here.

What is compared is the program at the precision its configuration states:
float32 storage with every matmul at ``Precision.DEFAULT``.  On a TPU that
means each operand is rounded to bfloat16 and the products are summed in
float32 (one MXU pass); on a CPU it means float32 throughout.  The
reference does exactly that and nothing less: it rounds each matmul's
operands to ``operand_dtype`` and multiplies at ``Precision.HIGHEST``, so
the products of the rounded values are exact and summed in float32.

``swiglu_lowp`` is the control: the same layer with every array held in a
lower precision (bfloat16 by default), as a PR that moved the layer's
activations and outputs to bfloat16 would compute it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def default_operand_dtype(platform: str):
    """What ``Precision.DEFAULT`` rounds a float32 matmul operand to."""
    return jnp.bfloat16 if platform == "tpu" else jnp.float32


def expand_mask(mask: jax.Array, block: int) -> jax.Array:
    return jnp.repeat(jnp.repeat(mask, block, 0), block, 1)


@functools.partial(jax.jit, static_argnames=("block",))
def masked_weights(mask, wg, wu, wd, *, block):
    """The pruned layer's dense float32 weights (zeros outside kept blocks)."""
    full = expand_mask(mask, block)
    f32 = jnp.float32
    return (wg.astype(f32) * full, wu.astype(f32) * full,
            wd.astype(f32) * full.T)


def _dot(a, b, operand_dtype):
    r = lambda t: t.astype(operand_dtype).astype(jnp.float32)  # noqa: E731
    return jnp.dot(r(a), r(b), precision=HIGHEST,
                   preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("operand_dtype",))
def swiglu(x, wg, wu, wd, *, operand_dtype=jnp.float32):
    """Reference layer on rows ``x`` (n, d) -> (n, d), float32."""
    x = x.astype(jnp.float32)
    g = _dot(x, wg, operand_dtype)
    u = _dot(x, wu, operand_dtype)
    return _dot(jax.nn.silu(g) * u, wd, operand_dtype)


@functools.partial(jax.jit, static_argnames=("dtype",))
def swiglu_lowp(x, wg, wu, wd, *, dtype=jnp.bfloat16):
    """Control: the layer with inputs, weights, intermediates and output
    all held in ``dtype`` (matmuls accumulate as the platform does)."""
    c = lambda t: t.astype(dtype)  # noqa: E731
    g = jnp.dot(c(x), c(wg))
    u = jnp.dot(c(x), c(wu))
    h = jax.nn.silu(g) * u
    return jnp.dot(h, c(wd)).astype(jnp.float32)


def in_blocks(fn, x, weights, rows: int):
    """Apply ``fn(x_rows, *weights)`` over ``x`` (n, d) in blocks of rows,
    so the largest layer's intermediates fit beside its weights."""
    outs = [fn(x[i:i + rows], *weights) for i in range(0, x.shape[0], rows)]
    return jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]


def widest_gap(out, ref) -> float:
    """max |out - ref| over max |ref|: the widest gap of one answer, as a
    share of its largest entry.  Non-finite output reads as infinity."""
    out = jnp.asarray(out, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    if out.shape != ref.shape:
        return float("inf")
    gap = jnp.max(jnp.abs(out - ref)) / (jnp.max(jnp.abs(ref)) + 1e-30)
    gap = float(gap)
    return gap if gap == gap and gap != float("inf") else float("inf")
