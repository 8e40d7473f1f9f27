"""Sharding rules: every arch's params/cache/batch get valid, exactly-
divisible argument shardings on a small mesh (same code path as the
production 16×16 / 2×16×16 meshes)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh

from repro.configs import ARCH_IDS, get_config
from repro.models import build_model
from repro.sharding import batch_sharding, cache_sharding, params_sharding


@pytest.fixture(scope="module")
def mesh():
    devs = np.asarray(jax.devices())
    if devs.size < 2:
        pytest.skip("needs >1 local device")
    return jax.make_mesh((devs.size // 2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def _check_divisible(tree_struct, shardings, mesh):
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for leaf, sh in zip(jax.tree.leaves(tree_struct),
                        jax.tree.leaves(
                            shardings,
                            is_leaf=lambda x: hasattr(x, "spec"))):
        spec = sh.spec
        for dim, part in zip(leaf.shape, tuple(spec)):
            if part is None:
                continue
            axes = part if isinstance(part, tuple) else (part,)
            prod = int(np.prod([sizes[a] for a in axes]))
            assert dim % prod == 0, (leaf.shape, spec)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_shardings_divisible(arch, mesh):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    struct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    shardings = params_sharding(struct, mesh, cfg)
    _check_divisible(struct, shardings, mesh)


@pytest.mark.parametrize("arch", ["smollm-360m", "jamba-v0.1-52b",
                                  "rwkv6-3b", "mixtral-8x7b",
                                  "seamless-m4t-large-v2"])
def test_cache_shardings_divisible(arch, mesh):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    struct = jax.eval_shape(lambda: model.init_cache(4, 32))
    shardings = cache_sharding(struct, mesh, cfg)
    _check_divisible(struct, shardings, mesh)


def test_batch_sharding_uneven_batch(mesh):
    batch = {"tokens": jax.ShapeDtypeStruct((3, 8), jnp.int32)}
    sh = batch_sharding(batch, mesh)
    # batch of 3 cannot shard over the data axis: must replicate
    assert sh["tokens"].spec == jax.sharding.PartitionSpec(None, None)


def test_sharded_forward_matches_single_device(mesh):
    """Same params, same batch: sharded jit == unsharded reference."""
    cfg = get_config("smollm-360m", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
    ref = np.asarray(model.logits(params, tok, remat=False)
                     .astype(jnp.float32))
    shardings = params_sharding(params, mesh, cfg)
    sharded = jax.tree.map(jax.device_put, params, shardings)
    with jax.set_mesh(mesh):
        out = jax.jit(lambda p, t: model.logits(p, t, remat=False))(
            sharded, tok)
    err = np.abs(np.asarray(out.astype(jnp.float32)) - ref).max()
    assert err / (np.abs(ref).max() + 1e-6) < 2e-2
