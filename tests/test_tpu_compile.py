"""Compile rehearsal: the streaming SpMSpM kernels at qwen2-1.5b FFN width,
compiled for a described (not attached) TPU v5e.

Nothing runs here: each test lowers a kernel with ``interpret=False`` for
one chip of a ``v5e:2x2`` topology and compiles it with the TPU compiler,
which refuses misaligned slices, over-budget VMEM and unpartitionable
kernels before any chip time is spent.  The platform check in
``repro.config`` sees the CPU in this process, so ``interpret=False`` is
passed explicitly.

The topology is described inside a module fixture (never at import or
collection time): only one process may load the TPU library, and every
test worker imports this file.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import flexagon_plan
from repro.kernels.stream import _stream_panel_spmm, _stream_spmm

D_MODEL, D_FF = 1536, 8960          # qwen2-1.5b (src/repro/configs)
BLOCK = 128
SPARSITY = 0.75


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # noqa: BLE001 — any failure: skip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one — keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def w_gate():
    """A 75%-block-sparse (d_model, d_ff) weight with 128-aligned blocks."""
    rng = np.random.default_rng(0)
    mask = rng.random((D_MODEL // BLOCK, D_FF // BLOCK)) >= SPARSITY
    full = np.repeat(np.repeat(mask, BLOCK, 0), BLOCK, 1)
    return (rng.standard_normal((D_MODEL, D_FF)) * full).astype(np.float32)


def _kernel_args(plan, sharding):
    """Shapes of what ``PallasBackend.execute`` hands the kernel, on
    ``sharding``: N-stationary plans run the transposed problem."""
    m, _, n = plan.shapes
    a_nnzb, b_nnzb = plan.a_layout.nnzb, plan.b_layout.nnzb
    if plan.dataflow.endswith("_n"):
        a_nnzb, b_nnzb, m, n = b_nnzb, a_nnzb, n, m
    blk = (BLOCK, BLOCK)
    a = jax.ShapeDtypeStruct((a_nnzb, *blk), jnp.float32, sharding=sharding)
    b = jax.ShapeDtypeStruct((b_nnzb, *blk), jnp.float32, sharding=sharding)
    sched = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.int32,
                                       sharding=sharding),
        plan.aux["stream_schedule"])
    grid = (-(-m // BLOCK), -(-n // BLOCK))
    return a, b, sched, grid, (m, n)


@pytest.mark.parametrize("tokens", [4, 512])
@pytest.mark.parametrize("dataflow,kernel", [
    ("ip_m", _stream_spmm),
    ("op_m", _stream_spmm),
    ("gust_m", _stream_panel_spmm),
    ("op_n", _stream_spmm),
])
def test_stream_kernel_compiles_for_v5e(one_chip, w_gate, dataflow, kernel,
                                        tokens):
    plan = flexagon_plan((tokens, D_MODEL), w_gate, dataflow=dataflow,
                         block_shape=(BLOCK,) * 3, backend="pallas",
                         verify=False)
    assert "dense" not in plan.aux      # 75% sparse: the kernel path
    a, b, sched, grid, shape = _kernel_args(plan, one_chip)
    compiled = kernel.lower(a, b, sched, out_grid=grid, out_shape=shape,
                            out_dtype=jnp.float32,
                            interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


#: the benchmark's matmuls: (tokens, d_in, d_out) of the Mixtral-8x7B
#: expert at decode16 and prefill1024 and of Chameleon-34B at decode32
BENCH_MATMULS = [(16, 4096, 14336), (16, 14336, 4096), (32, 8192, 22016),
                 (32, 22016, 8192), (1024, 14336, 4096)]


@pytest.mark.parametrize("tokens,d_in,d_out", BENCH_MATMULS)
def test_block_run_kernel_compiles_at_benchmark_widths(one_chip, tokens,
                                                       d_in, d_out):
    """The block-run kernel at the benchmark's real shapes (``ip_m``, 25%
    of the weight blocks kept): its VMEM (resident activations or the
    ring, which the 1024-token down projection's 56 MiB of activation
    blocks takes) fits, and it stays one ``_stream_spmm`` custom-call."""
    from repro.kernels.stream import StreamSchedule, a_resident

    mb, kb, nb = -(-tokens // BLOCK), d_in // BLOCK, d_out // BLOCK
    kept = kb * nb // 4
    w, r = mb * kept, mb * nb

    def ints(n):
        return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)

    sched = StreamSchedule(*[ints(w)] * 6, ints(r), ints(r), r, "dest",
                           ints(1), ints(1), ints(1))
    a = jax.ShapeDtypeStruct((mb * kb, BLOCK, BLOCK), jnp.float32,
                             sharding=one_chip)
    b = jax.ShapeDtypeStruct((kept, BLOCK, BLOCK), jnp.float32,
                             sharding=one_chip)
    assert a_resident(mb * kb * BLOCK * BLOCK * 4) == (tokens < 1024
                                                       or d_in < d_out)
    text = _stream_spmm.lower(a, b, sched, out_grid=(mb, nb),
                              out_shape=(tokens, d_out),
                              out_dtype=jnp.float32,
                              interpret=False).compile().as_text()
    kernels = [line.split("=")[0].strip() for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1 and kernels[0].startswith("%_stream_spmm")


def test_ffn_kernels_carry_their_matmul_scope(one_chip, w_gate):
    """Each of ``sparse_ffn_apply``'s three kernels keeps its matmul's
    ``jax.named_scope`` in its ``op_name`` metadata after compilation: the
    name a profiler trace attributes the kernel's device time by."""
    import dataclasses
    import re

    from repro.models.sparse_linear import (PlannedFFN, compress_ffn,
                                            sparse_ffn_apply)

    tokens = 16
    mask = np.abs(w_gate).reshape(D_MODEL // BLOCK, BLOCK,
                                  D_FF // BLOCK, BLOCK).sum((1, 3)) > 0
    params = {"w_gate": {"w": w_gate}, "w_up": {"w": w_gate},
              "w_down": {"w": np.ascontiguousarray(w_gate.T)},
              "block_mask": mask}
    entry = compress_ffn(params, tokens=tokens, backend="pallas",
                         block=BLOCK, verify=False).specialize(tokens)
    plans = [dataclasses.replace(p, interpret=False)
             for p in (entry.plan_in, entry.plan_out)]

    class Fixed:
        def __init__(self, weights):
            self.entry = PlannedFFN(*plans, *weights)

        def specialize(self, n):
            return self.entry

    weights = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (entry.w_gate, entry.w_up, entry.w_down))
    x = jax.ShapeDtypeStruct((1, tokens, D_MODEL), jnp.float32,
                             sharding=one_chip)
    text = jax.jit(lambda w, x: sparse_ffn_apply(Fixed(w), x)).lower(
        weights, x).compile().as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    scopes = sorted(re.search(r'op_name="[^"]*?/(ffn\.\w+)/', line).group(1)
                    for line in kernels)
    assert scopes == ["ffn.down", "ffn.gate", "ffn.up"]
    assert all(line.lstrip().startswith("%_stream_spmm") for line in kernels)


def _expert_masks(n, kb, nb, seed=0):
    """``n`` experts' block masks with an exact 25% of the blocks kept."""
    rng = np.random.default_rng(seed)
    masks = np.zeros((n, kb * nb), bool)
    for m in masks:
        m[rng.permutation(kb * nb)[: kb * nb // 4]] = True
    return masks.reshape(n, kb, nb)


#: DeepSeek-V3's routed experts: hidden 7168, expert width 2048; 32 held
#: experts, 1024 tokens routed top-8
MOE_D, MOE_F, MOE_HELD, MOE_TOKENS = 7168, 2048, 32, 1024


@pytest.mark.parametrize("d_in,d_out", [(MOE_D, MOE_F), (MOE_F, MOE_D)])
def test_grouped_kernel_compiles_at_expert_widths(one_chip, d_in, d_out):
    """The grouped block-run kernel for 32 stacked experts and every slot
    1024 tokens routed top-8 can fill: the slot stack is too large to hold,
    so it holds two slots' A row panels; its VMEM (those panels and the B
    ring) stays within a v5e core's 128 MiB, and it stays one
    ``_grouped_spmm`` custom-call."""
    import re

    from repro.kernels.stream import (_grouped_spmm, grouped_a_mode,
                                      row_schedules)
    from repro.models.sparse_moe import n_slots

    slots = n_slots(MOE_TOKENS, 8, MOE_HELD, BLOCK)
    sched = row_schedules(_expert_masks(MOE_HELD, d_in // BLOCK,
                                        d_out // BLOCK))
    kept = d_in * d_out // BLOCK ** 2 // 4
    panel_bytes = d_in // BLOCK * BLOCK * BLOCK * 4
    assert grouped_a_mode(slots, d_in // BLOCK, BLOCK * BLOCK * 4) == "panel"

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = _grouped_spmm.lower(
        sds((slots * d_in // BLOCK, BLOCK, BLOCK), jnp.float32),
        sds((MOE_HELD * kept + 1, BLOCK, BLOCK), jnp.float32),
        jax.tree.map(lambda x: sds(x.shape), sched), sds((slots,)), sds(()),
        out_cols=d_out, out_dtype=jnp.float32, interpret=False)
    vmem = [int(v) for v in re.findall(
        r'scoped_memory_configs\\22: \[\{[^}]*\\22size\\22: (\d+)',
        lowered.as_text())]
    assert len(vmem) == 1 and 2 * panel_bytes < vmem[0] <= 128 << 20
    text = lowered.compile().as_text()
    kernels = [line.split("=")[0].strip() for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert slots == 95 and len(kernels) == 1
    assert kernels[0].startswith("%_grouped_spmm")


def test_moe_layer_kernels_carry_their_scopes(one_chip, monkeypatch):
    """The whole MoE layer at DeepSeek-V3's widths compiles for a v5e: its
    three grouped kernels under ``moe.experts`` and the shared expert's
    three kernels under ``moe.shared``, each in its matmul's ``ffn.*``
    scope."""
    import re

    from repro import config
    from repro.kernels.stream import row_schedules
    from repro.models.sparse_linear import PlannedFFN, compress_ffn
    from repro.models.sparse_moe import (ExpertStack, RouterSpec,
                                         sparse_moe_apply)

    monkeypatch.setattr(config, "interpret_default", lambda: False)
    masks = _expert_masks(MOE_HELD, MOE_D // BLOCK, MOE_F // BLOCK)

    def sds(x, dtype=None):
        shape = x if isinstance(x, tuple) else np.shape(x)
        dtype = dtype or (x.dtype if hasattr(x, "dtype") else jnp.float32)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s_in = row_schedules(masks)
    s_out = row_schedules(masks.transpose(0, 2, 1))
    blocks = (BLOCK, BLOCK)
    kept = MOE_D * MOE_F // BLOCK ** 2 // 4
    experts = ExpertStack(
        sds((MOE_HELD * kept + 1, *blocks)),
        sds((MOE_HELD * kept + 1, *blocks)),
        sds((MOE_HELD * kept + 1, *blocks)),
        jax.tree.map(sds, s_in), jax.tree.map(sds, s_out), MOE_D, MOE_F)
    mask = _expert_masks(1, MOE_D // BLOCK, MOE_F // BLOCK, seed=1)[0]
    full = np.repeat(np.repeat(mask, BLOCK, 0), BLOCK, 1).astype(np.float32)
    params = {"w_gate": {"w": full}, "w_up": {"w": full},
              "w_down": {"w": np.ascontiguousarray(full.T)},
              "block_mask": mask.astype(np.float32)}
    entry = compress_ffn(params, tokens=MOE_TOKENS, backend="pallas",
                         block=BLOCK, verify=False).specialize(MOE_TOKENS)

    class Fixed:
        def __init__(self, weights):
            self.entry = PlannedFFN(entry.plan_in, entry.plan_out, *weights)

        def specialize(self, n):
            return self.entry

    weights = jax.tree.map(sds, (entry.w_gate, entry.w_up, entry.w_down))
    spec = RouterSpec(n_experts=256, n_group=8, topk_group=4, top_k=8,
                      scale=2.5)
    text = jax.jit(lambda h, w_r, b, e, w: sparse_moe_apply(
        h, w_r, b, e, Fixed(w), spec)).lower(
            sds((MOE_TOKENS, MOE_D)), sds((MOE_D, 256)), sds((256,)),
            experts, weights).compile().as_text()
    kernels = sorted(
        (line.lstrip().split(".")[0],
         re.search(r'op_name="[^"]*?/(moe\.\w+)/(ffn\.\w+)/', line).groups())
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line)
    assert kernels == sorted(
        [("%_grouped_spmm", ("moe.experts", f"ffn.{m}"))
         for m in ("gate", "up", "down")] +
        [("%_stream_spmm", ("moe.shared", f"ffn.{m}"))
         for m in ("gate", "up", "down")])
