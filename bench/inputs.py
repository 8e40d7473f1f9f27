"""Weights, block masks and activation pools, made on the device from a seed.

The one generator every configuration and traffic mix of the pruned-FFN
kind goes through: a configuration file gives the sizes, a traffic file
the tokens per call and the pool, and the seed everything else.  Every
seed gets the same sizes and the same number of kept blocks (the mask
keeps an exact count, placed uniformly at random), so seeds change which
blocks are kept and the values, never the amount of work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int) -> jax.Array:
    """A JAX key from any non-negative integer seed (wider than 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))


def kept_blocks(cfg: dict) -> int:
    """Blocks each weight matrix keeps: the exact share 1 - sparsity."""
    b = cfg["block"]
    total = (cfg["hidden_size"] // b) * (cfg["intermediate_size"] // b)
    return int(round(total * (1.0 - cfg["ffn_block_sparsity"])))


@functools.partial(jax.jit, static_argnames=("d", "f", "block", "kept",
                                             "dtype"))
def _weights(key, *, d, f, block, kept, dtype):
    km, kg, ku, kd = jax.random.split(key, 4)
    gd, gf = d // block, f // block
    order = jax.random.permutation(km, gd * gf)
    mask = jnp.zeros(gd * gf, jnp.float32).at[order[:kept]].set(1.0)
    dt = jnp.dtype(dtype)
    wg = jax.random.normal(kg, (d, f), dt) * (d ** -0.5)
    wu = jax.random.normal(ku, (d, f), dt) * (d ** -0.5)
    wd = jax.random.normal(kd, (f, d), dt) * (f ** -0.5)
    return mask.reshape(gd, gf), wg, wu, wd


def make_weights(cfg: dict, seed: int):
    """(block_mask (d/b, f/b), w_gate (d, f), w_up (d, f), w_down (f, d)),
    dense and unmasked, in the configuration's dtype, in one device call."""
    key = jax.random.fold_in(key_from_seed(seed), 0)
    return _weights(key, d=cfg["hidden_size"], f=cfg["intermediate_size"],
                    block=cfg["block"], kept=kept_blocks(cfg),
                    dtype=cfg["dtype"])


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _pool(key, *, shape, dtype):
    n = shape[0]
    xs = jax.random.normal(key, shape, jnp.dtype(dtype))
    return tuple(xs[i] for i in range(n))


def make_pool(cfg: dict, traffic: dict, seed: int):
    """The traffic's pool of distinct activation batches, each (batch, seq,
    d_model), made in one device call; calls cycle through it in order."""
    key = jax.random.fold_in(key_from_seed(seed), 1)
    shape = (traffic["pool"], traffic["batch"], traffic["seq"],
             cfg["hidden_size"])
    return _pool(key, shape=shape, dtype=cfg["dtype"])


def sample_slots(traffic: dict, seed: int, n: int):
    """The pool slots whose in-window answers are compared, drawn from the
    seed (sorted, distinct)."""
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)).spawn(3)[2])
    k = min(n, traffic["pool"])
    return sorted(int(s) for s in rng.choice(traffic["pool"], k,
                                              replace=False))
