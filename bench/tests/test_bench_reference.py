"""The plain reference against the program's own dense FFN, at smoke size."""
import jax
import jax.numpy as jnp
import numpy as np

import bench_tiny  # noqa: F401
from bench import inputs, reference
from repro.models.ffn import ffn_apply

CFG = {"hidden_size": 256, "intermediate_size": 384, "block": 128,
       "ffn_block_sparsity": 0.5, "dtype": "float32"}


def _layer(seed=3):
    mask, wg, wu, wd = inputs.make_weights(CFG, seed)
    params = {"w_gate": {"w": wg}, "w_up": {"w": wu}, "w_down": {"w": wd},
              "block_mask": mask}
    x = jax.random.normal(jax.random.key(seed), (2, 5, CFG["hidden_size"]))
    return params, reference.masked_weights(mask, wg, wu, wd, block=128), x


def test_reference_matches_the_models_block_masked_ffn():
    params, w, x = _layer()
    ours = ffn_apply(params, None, x).astype(jnp.float32)   # bf16 compute
    ref = reference.swiglu(x.reshape(-1, 256), *w).reshape(x.shape)
    assert reference.widest_gap(ours, ref) < 2e-2
    lowp = reference.swiglu_lowp(x.reshape(-1, 256), *w).reshape(x.shape)
    assert reference.widest_gap(ours, lowp) < 1e-2


def test_masked_weights_zero_every_dropped_block():
    params, (wg, wu, wd), _ = _layer()
    mask = np.asarray(params["block_mask"])
    blocks = np.asarray(wg).reshape(2, 128, 3, 128).swapaxes(1, 2)
    for i, j in zip(*np.nonzero(mask == 0)):
        assert not blocks[i, j].any()
    assert np.array_equal(np.asarray(wd), np.asarray(wd) * np.kron(
        mask.T, np.ones((128, 128))))


def test_blocks_of_rows_give_the_same_answer():
    _, w, x = _layer()
    x2 = x.reshape(-1, 256)
    whole = reference.swiglu(x2, *w)
    parts = reference.in_blocks(reference.swiglu, x2, w, rows=3)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-6, atol=1e-6)


def test_widest_gap_reads_non_finite_and_wrong_shapes_as_infinite():
    a = jnp.ones((4, 4))
    assert reference.widest_gap(a, a) == 0.0
    assert reference.widest_gap(a.at[0, 0].set(jnp.nan), a) == float("inf")
    assert reference.widest_gap(a[:2], a) == float("inf")
    assert reference.widest_gap(a * 1.5, a) == 0.5
