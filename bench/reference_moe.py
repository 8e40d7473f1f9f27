"""Plain reference of the pruned MoE FFN stack, its route check, and its
control.

    x <- x + FFN(RMSNorm(x)),  eps from the configuration, per layer;
    layer 0: the dense pruned SwiGLU FFN;
    MoE layers: s = sigmoid(h W_r) (float32, Precision.HIGHEST);
      selection on z = s + b: groups scored by the sum of their two largest
      z, the top ``topk_group`` groups kept, the top ``top_k`` experts of
      those selected; g_i = scale * s_i / sum_selected s_j;
      y = FFN_shared(h) + sum over selected held experts of g_i FFN_i(h).

Straightforward ``jax.numpy`` in float32 that imports nothing of the
program: every weight is made again from the seed by ``inputs_moe``, one
layer and one expert at a time, so that it fits beside nothing else once
the program has released its state.  Each FFN matmul rounds its operands as
``reference.swiglu`` does (the stated ``Precision.DEFAULT``).

:func:`forward` takes optional forced routes: the program's selection for
each MoE layer.  Forced, every layer still computes its own selection from
its own activations, so that a route of the program can be held against
the reference's scores of the same input.  ``lowp=True`` is the control:
every array, the router included, in bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import inputs_moe, reference

HIGHEST = jax.lax.Precision.HIGHEST
BUCKET = 128            # rows of an expert's call are padded to a power of
                        # two times this, so few shapes compile


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def rms_norm(x, w, *, eps, dtype=jnp.float32):
    x = x.astype(dtype)
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
            * w).astype(dtype)


@functools.partial(jax.jit, static_argnames=("dtype",))
def scores(h, w, b, *, dtype=jnp.float32):
    """(s, z): sigmoid scores and the bias-corrected selection scores."""
    if dtype == jnp.float32:
        u = jnp.dot(h, w, precision=HIGHEST)
    else:
        u = jnp.dot(h.astype(dtype), w.astype(dtype)).astype(jnp.float32)
    s = jax.nn.sigmoid(u).astype(dtype).astype(jnp.float32)
    return s, s + b


@functools.partial(jax.jit, static_argnames=("n_group", "topk_group",
                                             "top_k"))
def select(z, *, n_group, topk_group, top_k):
    """The experts each row selects (n, top_k), and the group scores."""
    n, e = z.shape
    gs = jax.lax.top_k(z.reshape(n, n_group, e // n_group), 2)[0].sum(-1)
    _, groups = jax.lax.top_k(gs, topk_group)
    keep = jax.nn.one_hot(groups, n_group).sum(1) > 0
    masked = jnp.where(jnp.repeat(keep, e // n_group, axis=1), z, -jnp.inf)
    return jax.lax.top_k(masked, top_k)[1], gs


@functools.partial(jax.jit, static_argnames=("n_group", "topk_group",
                                             "top_k"))
def tie_gaps(z, ids, *, n_group, topk_group, top_k):
    """How far the reference's scores ``z`` are from selecting ``ids``.

    Returns ``(differ, group_gap, expert_gap)`` per row; the gaps are 0
    where ``ids`` is the reference's own selection.  ``group_gap``: how far
    the least of ``ids``'s groups outside the reference's top groups lies
    below its ``topk_group``-th group score.  ``expert_gap``: with the
    groups of ``ids`` kept (and the reference's best others up to
    ``topk_group``), how far the least of ``ids``'s experts outside the
    reference's selection among them lies below the least it selects.  A
    near-tie reads close to 0 in both; ``ids`` spread over more than
    ``topk_group`` groups reads infinite.
    """
    n, e = z.shape
    per = e // n_group
    own, gs = select(z, n_group=n_group, topk_group=topk_group, top_k=top_k)
    in_ids = jax.nn.one_hot(ids, e).sum(1) > 0
    differ = jnp.any((jax.nn.one_hot(own, e).sum(1) > 0) != in_ids, axis=1)
    id_groups = jax.nn.one_hot(ids // per, n_group).sum(1) > 0
    gs_cut = jax.lax.top_k(gs, topk_group)[0][:, -1]
    group_gap = jnp.max(jnp.where(id_groups & (gs < gs_cut[:, None]),
                                  gs_cut[:, None] - gs, 0.0), axis=1)
    # the reference's selection within the groups ``ids`` drew from
    _, groups = jax.lax.top_k(jnp.where(id_groups, jnp.inf, gs), topk_group)
    keep = jax.nn.one_hot(groups, n_group).sum(1) > 0
    within = jax.lax.top_k(jnp.where(jnp.repeat(keep, per, axis=1), z,
                                     -jnp.inf), top_k)[1]
    in_within = jax.nn.one_hot(within, e).sum(1) > 0
    z_cut = jnp.min(jnp.where(in_within, z, jnp.inf), axis=1)
    expert_gap = jnp.max(jnp.where(in_ids & ~in_within, z_cut[:, None] - z,
                                   0.0), axis=1)
    spread = id_groups.sum(1) > topk_group
    expert_gap = jnp.where(spread, jnp.inf, expert_gap)
    return (differ, jnp.where(differ, group_gap, 0.0),
            jnp.where(differ, expert_gap, 0.0))


def _ffn_rows(x, w, cfg, operand_dtype, lowp):
    if lowp:
        return reference.in_blocks(reference.swiglu_lowp, x, w,
                                   cfg["reference_rows"])
    return reference.in_blocks(
        lambda xr, *ws: reference.swiglu(xr, *ws, operand_dtype=operand_dtype),
        x, w, cfg["reference_rows"])


def _masked(parts, b):
    return reference.masked_weights(*parts, block=b)


@jax.jit
def _add_rows(y, tok, gate, out):
    return y.at[tok].add(gate[:, None] * out, mode="drop")


def _bucket(n: int) -> int:
    """Rows of an expert's call: ``n`` padded to a power of two times
    ``BUCKET``, so that few shapes compile."""
    return BUCKET * 2 ** int(np.ceil(np.log2(-(-n // BUCKET))))


def forward(cfg: dict, seed: int, x, *, forced=None, operand_dtype=jnp.float32,
            lowp: bool = False):
    """The stack on rows ``x`` (n, d); returns ``(y, routes)``.

    ``forced``: None, or per MoE layer the selected experts (n, top_k) to
    use in place of the reference's own.  ``routes``: per MoE layer
    ``(own ids, differ, group_gap, expert_gap)``: the reference's own
    selection, and where and by how much the used selection departs from
    it (:func:`tie_gaps`).  Every device operation sees shapes fixed by
    the configuration or padded to a bucket, so a run compiles a handful
    of programs whatever the routing.
    """
    b, eps = cfg["block"], cfg["rms_norm_eps"]
    dt = jnp.bfloat16 if lowp else jnp.float32
    kw = dict(n_group=cfg["n_group"], topk_group=cfg["topk_group"],
              top_k=cfg["num_experts_per_tok"])
    x = jnp.asarray(x, jnp.float32).astype(dt)
    ones = inputs_moe.norm(cfg)
    for _ in range(cfg["dense_layers"]):
        w = _masked(inputs_moe.dense_ffn(cfg, seed), b)
        x = x + _ffn_rows(rms_norm(x, ones, eps=eps, dtype=dt), w, cfg,
                          operand_dtype, lowp).astype(dt)
        del w
    routes = []
    for layer in range(1, cfg["moe_layers"] + 1):
        h = rms_norm(x, ones, eps=eps, dtype=dt)
        w_r, bias = inputs_moe.router(cfg, seed, layer)
        s, z = scores(h, w_r, bias, dtype=dt)
        own = select(z, **kw)[0]
        ids = own if forced is None else jnp.asarray(forced[layer - 1])
        routes.append((own, *tie_gaps(z, ids, **kw)))
        sel = jnp.take_along_axis(s, ids, axis=1)
        gates = np.asarray(cfg["routed_scaling_factor"] * sel /
                           jnp.sum(sel, -1, keepdims=True))
        y = _ffn_rows(h, _masked(inputs_moe.shared_ffn(cfg, seed, layer), b),
                      cfg, operand_dtype, lowp).astype(jnp.float32)
        ids_h = np.asarray(ids)
        hp = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), h.dtype)])
        for e in inputs_moe.held(cfg):
            tok, slot = np.nonzero(ids_h == e)
            if tok.size == 0:
                continue
            pad = np.full(_bucket(tok.size), h.shape[0], np.int32)
            pad[:tok.size] = tok
            gate = np.zeros(pad.size, np.float32)
            gate[:tok.size] = gates[tok, slot]
            w = _masked(inputs_moe.expert_ffn(cfg, seed, layer, e), b)
            out = _ffn_rows(jnp.take(hp, pad, axis=0), w, cfg, operand_dtype,
                            lowp).astype(jnp.float32)
            y = _add_rows(y, pad, gate, out)
            del w
        x = x + y.astype(dt)
    return x.astype(jnp.float32), routes
