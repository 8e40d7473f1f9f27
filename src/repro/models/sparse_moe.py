"""Sparse MoE FFN for serving: a node-limited sigmoid router over every
expert, and one holder's block-pruned experts run dropless through the
planned Pallas kernels.

The layer is DeepSeek-V3's (arXiv:2412.19437 §2.1.2) as one holder of an
expert-parallel deployment sees it:

- router: ``s = sigmoid(h @ W_r)`` in float32 at ``Precision.HIGHEST``;
  selection on ``s + b`` (``b`` the per-expert correction bias, used for
  selection only): each group of experts scores the sum of its two largest
  entries, the top ``topk_group`` groups stay, and the top ``top_k``
  experts among them are selected; gates ``g_i = scale * s_i / sum s_j``
  over the selected;
- output: ``FFN_shared(h) + sum over selected and held experts of
  g_i * FFN_i(h)``.  A token that selects none of the held experts gets
  the shared expert alone: the held experts' share of the layer.

Dispatch groups the assignments by held expert and pads each expert to
whole ``block``-row slots.  Nothing is dropped: the slot count is the most
that any routing can fill, ``sum_e ceil(n_e / block)`` of them are live,
and the grouped kernels (:func:`repro.kernels.stream.grouped_stream_spmm`)
walk the live slots only, so the executed block pairs scale with
``sum_e ceil(n_e / block)`` and an expert with no rows fetches none of its
blocks.  The routing stays on the device: one compiled program serves
every routing, with no host sync.

Phase 1 plans a layer's experts together from their block masks alone
(:func:`compress_experts`: one stacked schedule per weight orientation),
and packs each expert's kept blocks on the device as its dense weights
are loaded, a few experts at a time.

Observability (:mod:`repro.obs`): ``moe.compress`` spans and the
``moe.compress_s`` histogram in set-up, gauges ``moe.held_experts``,
``moe.block_pairs_max`` (the most block pairs one call of the last traced
layer can run) and ``moe.a_panel_bytes`` (the bytes of one slot's A row
panel its grouped kernels hold in VMEM, summed over gate, up and down; 0
when they hold the whole A stack or stream it); at run time the named
scopes ``moe.route``, ``moe.dispatch``, ``moe.experts`` (with
``ffn.gate`` / ``ffn.up`` / ``ffn.act`` / ``ffn.down`` inside),
``moe.combine`` and ``moe.shared`` (around
:func:`repro.models.sparse_linear.sparse_ffn_apply`).  Each call
also returns its live rows and executed slots per held expert, a small
int32 array the caller reads when it likes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..kernels.stream import StreamSchedule, grouped_a_mode, \
    grouped_stream_spmm, row_schedules
from .sparse_linear import sparse_ffn_apply

__all__ = ["ExpertStack", "RouterSpec", "compress_experts", "rms_norm",
           "route", "n_slots", "sparse_moe_apply", "moe_block_apply",
           "ffn_block_apply"]

HIGHEST = jax.lax.Precision.HIGHEST


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ExpertStack:
    """A layer's held experts, planned and packed together.

    ``w_gate``/``w_up`` (E * W_in + 1, b, b) and ``w_down`` (E * W_out +
    1, b, b) are the kept blocks, expert-major, in the order of
    ``sched_in`` / ``sched_out`` (:func:`repro.kernels.stream.row_schedules`
    of the experts' (d/b, f/b) and (f/b, d/b) block masks), then one zero
    block for the output columns an expert keeps no block of.
    """

    w_gate: jax.Array
    w_up: jax.Array
    w_down: jax.Array
    sched_in: StreamSchedule
    sched_out: StreamSchedule
    d_model: int
    d_ff: int

    def tree_flatten(self):
        return ((self.w_gate, self.w_up, self.w_down, self.sched_in,
                 self.sched_out), (self.d_model, self.d_ff))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def n_experts(self) -> int:
        return int(self.sched_in.a_slot.shape[0])

    @property
    def block(self) -> int:
        return int(self.w_gate.shape[1])

    def pairs_per_slot(self) -> int:
        """Block pairs gate, up and down run for one live slot."""
        return 2 * int(self.sched_in.a_slot.shape[1]) + \
            int(self.sched_out.a_slot.shape[1])


@dataclasses.dataclass(frozen=True)
class RouterSpec:
    """The router's published settings (static under jit)."""

    n_experts: int          # experts routed over, held here or not
    n_group: int
    topk_group: int
    top_k: int
    scale: float            # routed_scaling_factor


#: experts loaded and packed at a time by :func:`compress_experts`
PACK_CHUNK = 8


def _kept_blocks(masks: np.ndarray):
    """(k, j, kept) of each expert's kept blocks in column-major order, the
    order of its B slots in :func:`row_schedules`, padded to the most any
    expert keeps: (E, W) each."""
    width = int(masks.sum((1, 2)).max())
    out = np.zeros((3, masks.shape[0], width), np.int32)
    for e, m in enumerate(masks):
        js, ks = np.nonzero(m.T)
        out[0, e, :js.size], out[1, e, :js.size] = ks, js
        out[2, e, :js.size] = 1
    return out


@functools.partial(jax.jit, static_argnames=("block",))
def _pack(w, ks, js, kept, *, block):
    """Dense weights ``w`` (n, K, N) -> the blocks (k, j) of each expert,
    (n * W, b, b); pad slots (``kept`` 0) are zero."""
    n, k, m = w.shape
    blocks = w.astype(jnp.float32).reshape(
        n, k // block, block, m // block, block).transpose(0, 1, 3, 2, 4)
    got = blocks[jnp.arange(n)[:, None], ks, js] * kept[..., None, None]
    return got.reshape(-1, block, block)


def compress_experts(masks, load: Callable, *,
                     block: int = 128) -> ExpertStack:
    """Phase 1 and packing for a layer's held experts, planned together.

    ``masks`` (E, d/b, f/b): each expert's gate/up block pattern; down
    keeps the transpose.  ``load(lo, hi)`` gives the dense weights of
    experts ``lo..hi-1`` as ``(w_gate (n, d, f), w_up (n, d, f), w_down
    (n, f, d))``, on the device or the host; blocks outside the masks are
    dropped.  Experts are loaded and packed :data:`PACK_CHUNK` at a time,
    so the dense weights of a whole layer never sit in memory at once.
    """
    masks = np.asarray(masks, bool)
    n = masks.shape[0]
    t0 = obs.now_ns()
    with obs.span("moe.compress", experts=n):
        masks_t = masks.transpose(0, 2, 1)
        order = {"in": _kept_blocks(masks), "out": _kept_blocks(masks_t)}
        packed = {"gate": [], "up": [], "down": []}
        for lo in range(0, n, PACK_CHUNK):
            hi = min(n, lo + PACK_CHUNK)
            weights = dict(zip(("gate", "up", "down"), load(lo, hi)))
            for name, w in weights.items():
                ks, js, kept = order["out" if name == "down" else "in"][
                    :, lo:hi]
                packed[name].append(_pack(w, ks, js, kept, block=block))
            del weights
        zero = jnp.zeros((1, block, block), jnp.float32)
        stack = ExpertStack(
            *(jnp.concatenate(packed[k] + [zero])
              for k in ("gate", "up", "down")),
            jax.device_put(row_schedules(masks)),
            jax.device_put(row_schedules(masks_t)),
            masks.shape[1] * block, masks.shape[2] * block)
        jax.block_until_ready(stack)
    obs.get_registry().histogram("moe.compress_s").observe(
        (obs.now_ns() - t0) / 1e9)
    obs.get_registry().gauge("moe.held_experts").set(n)
    return stack


def rms_norm(x, w, eps: float):
    """``x / rms(x) * w`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def route(h, w_router, bias, spec: RouterSpec):
    """Node-limited sigmoid routing of rows ``h`` (T, d) over every expert.

    Returns ``(ids (T, top_k) int32, gates (T, top_k) float32)``.
    """
    t = h.shape[0]
    u = jnp.dot(h.astype(jnp.float32), w_router.astype(jnp.float32),
                precision=HIGHEST, preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(u)
    z = s + bias.astype(jnp.float32)
    per_group = spec.n_experts // spec.n_group
    group_score = jax.lax.top_k(z.reshape(t, spec.n_group, per_group),
                                2)[0].sum(-1)
    _, groups = jax.lax.top_k(group_score, spec.topk_group)
    keep = jnp.zeros((t, spec.n_group), bool).at[
        jnp.arange(t)[:, None], groups].set(True)
    z = jnp.where(jnp.repeat(keep, per_group, axis=1), z, -jnp.inf)
    _, ids = jax.lax.top_k(z, spec.top_k)
    sel = jnp.take_along_axis(s, ids, axis=1)
    sel = sel / jnp.sum(sel, -1, keepdims=True)
    return ids.astype(jnp.int32), sel * spec.scale


def n_slots(tokens: int, top_k: int, n_held: int, block: int) -> int:
    """The most ``block``-row slots any routing of ``tokens`` can fill:
    ``sum_e ceil(n_e / block)`` with ``sum_e n_e <= tokens * min(top_k,
    n_held)`` and each ``n_e <= tokens``."""
    per_expert = -(-tokens // block)
    assigned = tokens * min(top_k, n_held)
    return max(1, min(n_held * per_expert,
                      (assigned + n_held * (block - 1)) // block))


def _dispatch(ids, held_lo: int, n_held: int, block: int, slots: int):
    """Row of each assignment in the expert-grouped, slot-padded order
    (``slots * block`` for an assignment to an expert not held), each
    slot's expert, the live slot count and per-expert (rows, slots)."""
    key = ids.reshape(-1) - held_lo
    held = (key >= 0) & (key < n_held)
    key = jnp.where(held, key, n_held)
    onehot = (key[:, None] == jnp.arange(n_held + 1)[None]).astype(jnp.int32)
    seen = jnp.cumsum(onehot, axis=0)
    rank = jnp.take_along_axis(seen, key[:, None], 1)[:, 0] - 1
    rows = seen[-1, :n_held]
    used = (rows + block - 1) // block
    ends = jnp.cumsum(used)
    first = ends - used
    dest = jnp.where(held, first[jnp.minimum(key, n_held - 1)] * block + rank,
                     slots * block)
    slot_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(slots), side="right"), n_held - 1)
    counts = jnp.stack([rows, used]).astype(jnp.int32)
    return dest.reshape(ids.shape), slot_expert.astype(jnp.int32), \
        ends[-1].astype(jnp.int32), counts


def _slot_blocks(h, token, slots: int, block: int):
    """The dispatched rows ``h[token]`` (a zero row for ``token == T``) as
    slot-major blocks (slots * d/b, b, b): the grouped kernels' A side."""
    t, d = h.shape
    rows = jnp.concatenate([h, jnp.zeros((1, d), h.dtype)]).reshape(
        t + 1, d // block, block)[token.reshape(slots, block)]
    return rows.transpose(0, 2, 1, 3).reshape(-1, block, block)


def sparse_moe_apply(h, w_router, bias, experts: ExpertStack, shared,
                     spec: RouterSpec, *, held_lo: int = 0):
    """The held experts' share of the MoE FFN on rows ``h`` (T, d).

    ``experts`` holds experts ``held_lo .. held_lo + E - 1`` of the
    ``spec.n_experts`` the router selects from; ``shared`` is the shared
    expert (anything :func:`sparse_ffn_apply` takes).  Returns ``(y (T, d)
    float32, ids (T, top_k) int32, counts (2, E) int32)``: the output, the
    selected experts, and per held expert its live rows and executed
    slots.
    """
    t, d = h.shape
    e, b = experts.n_experts, experts.block
    slots = n_slots(t, spec.top_k, e, b)
    obs.get_registry().gauge("moe.block_pairs_max").set(
        slots * experts.pairs_per_slot())
    blk = b * b * jnp.dtype(jnp.float32).itemsize
    obs.get_registry().gauge("moe.a_panel_bytes").set(sum(
        ka * blk for ka in (d // b, d // b, experts.d_ff // b)
        if grouped_a_mode(slots, ka, blk) == "panel"))
    h = h.astype(jnp.float32)
    with jax.named_scope("moe.route"):
        ids, gates = route(h, w_router, bias, spec)
    with jax.named_scope("moe.dispatch"):
        dest, slot_expert, live, counts = _dispatch(ids, held_lo, e, b, slots)
        token = jnp.full((slots * b,), t, jnp.int32).at[dest.reshape(-1)].set(
            jnp.arange(t * spec.top_k, dtype=jnp.int32) // spec.top_k,
            mode="drop")
        xs = _slot_blocks(h, token, slots, b)
    with jax.named_scope("moe.experts"):
        # outputs stay in the kernels' slot-major block layout: gate and
        # up's blocks are down's A blocks as they are
        def grouped(a, w, sched, cols):
            return grouped_stream_spmm(a, w, sched, slot_expert, live,
                                       out_cols=cols)
        with jax.named_scope("ffn.gate"):
            g = grouped(xs, experts.w_gate, experts.sched_in, experts.d_ff)
        with jax.named_scope("ffn.act"):
            g = jax.nn.silu(g)
        with jax.named_scope("ffn.up"):
            u = grouped(xs, experts.w_up, experts.sched_in, experts.d_ff)
        with jax.named_scope("ffn.act"):
            a = (g * u).reshape(-1, b, b)
        with jax.named_scope("ffn.down"):
            y_blocks = grouped(a, experts.w_down, experts.sched_out, d)
    with jax.named_scope("moe.shared"):
        y = sparse_ffn_apply(shared, h[None]).reshape(t, d)
    with jax.named_scope("moe.combine"):
        # each (token, choice)'s row of its slot; a choice not held reads
        # the out-of-bounds slot, filled with zeros
        rows = y_blocks.at[dest // b, :, dest % b, :].get(
            mode="fill", fill_value=0).reshape(t, spec.top_k, d)
        y = y + jnp.sum(rows * gates[..., None], axis=1)
    return y, ids, counts


def moe_block_apply(x, norm_w, w_router, bias, experts: ExpertStack, shared,
                    spec: RouterSpec, *, eps: float, held_lo: int = 0):
    """``x + MoE(RMSNorm(x))`` on ``x`` (B, S, d); returns the new ``x``
    and :func:`sparse_moe_apply`'s ids and counts."""
    shape = x.shape
    h = rms_norm(x, norm_w, eps).reshape(-1, shape[-1])
    y, ids, counts = sparse_moe_apply(h, w_router, bias, experts, shared,
                                      spec, held_lo=held_lo)
    return x + y.reshape(shape).astype(x.dtype), ids, counts


def ffn_block_apply(x, norm_w, ffn, *, eps: float):
    """``x + FFN(RMSNorm(x))`` for a dense pruned FFN (``sparse_ffn_apply``)."""
    return x + sparse_ffn_apply(ffn, rms_norm(x, norm_w, eps)).astype(x.dtype)
