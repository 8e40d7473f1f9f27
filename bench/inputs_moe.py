"""Weights, block masks, router and topic-skewed activation pools of the
pruned MoE FFN stack, made on the device from a seed.

The stack is one dense FFN layer then MoE layers (layer 0 is the dense
one).  Every weight matrix comes from ``inputs._weights`` with a key of its
own, so a seed fixes the kept-block count (an exact share, placed at random)
and the values, and any one expert can be made again alone: the reference
regenerates each expert as it needs it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .inputs import _weights, key_from_seed

EXPERT_PART = 16        # part ids below this name a layer's other weights


def _key(seed: int, layer: int, part: int) -> jax.Array:
    return jax.random.fold_in(
        jax.random.fold_in(key_from_seed(seed), 100 + layer), part)


def _kept(cfg: dict, d: int, f: int) -> int:
    b = cfg["block"]
    return int(round((d // b) * (f // b) * (1.0 - cfg["ffn_block_sparsity"])))


def _ffn(cfg: dict, seed: int, layer: int, part: int, f: int):
    d = cfg["hidden_size"]
    return _weights(_key(seed, layer, part), d=d, f=f, block=cfg["block"],
                    kept=_kept(cfg, d, f), dtype=cfg["dtype"])


def dense_ffn(cfg: dict, seed: int):
    """(mask, w_gate, w_up, w_down) of the leading dense layer."""
    return _ffn(cfg, seed, 0, 0, cfg["intermediate_size"])


def shared_ffn(cfg: dict, seed: int, layer: int):
    """(mask, w_gate, w_up, w_down) of MoE layer ``layer``'s shared expert."""
    return _ffn(cfg, seed, layer, 0, cfg["moe_intermediate_size"])


def held(cfg: dict) -> range:
    """The routed experts this chip holds: routing group ``held_group``."""
    lo = cfg["held_group"] * cfg["n_routed_experts"]
    return range(lo, lo + cfg["n_routed_experts"])


def expert_ffn(cfg: dict, seed: int, layer: int, expert: int):
    """(mask, w_gate, w_up, w_down) of routed expert ``expert`` (its id
    among all the router selects from) of ``layer``."""
    return _ffn(cfg, seed, layer, EXPERT_PART + expert,
                cfg["moe_intermediate_size"])


def experts(cfg: dict, seed: int, layer: int, lo: int, hi: int):
    """Dense weights (w_gate, w_up, w_down) of the held experts ``lo..hi-1``
    (numbered among the held), stacked on a leading expert axis."""
    ids = held(cfg)[lo:hi]
    parts = [expert_ffn(cfg, seed, layer, e)[1:] for e in ids]
    return tuple(jnp.stack(x) for x in zip(*parts))


@functools.partial(jax.jit, static_argnames=("d", "f", "block", "kept"))
def _masks(keys, *, d, f, block, kept):
    gd, gf = d // block, f // block

    def one(key):       # the mask ``_weights`` makes from the same key
        order = jax.random.permutation(jax.random.split(key, 4)[0], gd * gf)
        return jnp.zeros(gd * gf, jnp.float32).at[order[:kept]].set(1.0)

    return jax.vmap(one)(keys).reshape(-1, gd, gf)


def expert_masks(cfg: dict, seed: int, layer: int) -> np.ndarray:
    """Block masks (E, d/b, f/b) of a layer's held experts, as the weights
    :func:`expert_ffn` makes have them, without making the weights."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    keys = jnp.stack([_key(seed, layer, EXPERT_PART + e) for e in held(cfg)])
    return np.asarray(_masks(keys, d=d, f=f, block=cfg["block"],
                             kept=_kept(cfg, d, f))) > 0


def routed_total(cfg: dict) -> int:
    """Experts the router selects from: the published count."""
    return cfg["published"]["n_routed_experts"]


@functools.partial(jax.jit, static_argnames=("d", "n"))
def _router(key, *, d, n):
    kw, kb = jax.random.split(key)
    w = jax.random.normal(kw, (d, n), jnp.float32) * (d ** -0.5)
    b = jax.random.normal(kb, (n,), jnp.float32) * 0.01
    return w, b


def router(cfg: dict, seed: int, layer: int):
    """(W_r (d, n_total) float32, correction bias (n_total,)) of a layer."""
    return _router(_key(seed, layer, 1), d=cfg["hidden_size"],
                   n=routed_total(cfg))


def norm(cfg: dict) -> jax.Array:
    """RMSNorm weights: ones."""
    return jnp.ones((cfg["hidden_size"],), jnp.float32)


@functools.partial(jax.jit, static_argnames=("topics", "d", "zipf"))
def _topics(key, *, topics, d, zipf):
    centroids = jax.random.normal(key, (topics, d), jnp.float32)
    logp = -zipf * jnp.log(jnp.arange(1, topics + 1, dtype=jnp.float32))
    return centroids, logp - jax.nn.logsumexp(logp)


@functools.partial(jax.jit, static_argnames=("rows", "noise", "dtype"))
def _batch(key, centroids, logp, *, rows, noise, dtype):
    kt, kn = jax.random.split(key)
    c = centroids[jax.random.categorical(kt, logp, shape=(rows,))]
    d = c.shape[-1]
    scale = noise * jnp.linalg.norm(c, axis=-1, keepdims=True) / np.sqrt(d)
    return (c + scale * jax.random.normal(kn, c.shape, jnp.float32)).astype(
        jnp.dtype(dtype))


def make_pool(cfg: dict, traffic: dict, seed: int):
    """The traffic's pool of activation batches (batch, seq, d): each token
    is one of ``topics`` centroids drawn from the seed, chosen with Zipf
    (s = ``zipf``) weights, plus noise of ``noise`` times its norm."""
    key = jax.random.fold_in(key_from_seed(seed), 1)
    d = cfg["hidden_size"]
    centroids, logp = _topics(jax.random.fold_in(key, 0),
                              topics=traffic["topics"], d=d,
                              zipf=float(traffic["zipf"]))
    rows = traffic["batch"] * traffic["seq"]
    return tuple(
        _batch(jax.random.fold_in(key, 1 + i), centroids, logp, rows=rows,
               noise=float(traffic["noise"]), dtype=cfg["dtype"]).reshape(
                   traffic["batch"], traffic["seq"], d)
        for i in range(traffic["pool"]))
