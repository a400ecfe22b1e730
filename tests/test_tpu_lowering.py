"""AOT lowering check against a TPU v5e topology — needs no chip.

libtpu can compile for devices it cannot run on:
``jax.experimental.topologies.get_topology_desc`` hands out four
``TpuDevice`` handles, and every program below is lowered AND compiled
for them from this CPU host.  What that proves is that Mosaic accepts
the kernels and that the SPMD partitioner accepts the programs around
them — the two things no interpret-mode test can see (PR 21: the
4-device decoder step died with "Mosaic kernels cannot be
automatically partitioned", and ``int8_kv_attention`` failed its block
specs at every shape).  It proves nothing about speed or numerics.

Everything goes through the REAL dispatch sites: the kernel registry
resolves ``pallas`` because the installed mesh's devices are TPUs
(``distributed.mesh.target_platform``), never because a test forced a
mode.

Marked ``slow`` (the tier-1 command runs ``-m 'not slow'`` under an
870 s budget this file must not eat into); ``tools/run_tier1.sh`` runs
it as an always-on extra pass.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.ops.pallas import registry as kreg

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def topo_devices():
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    assert len(topo.devices) == 4
    assert topo.devices[0].platform == "tpu"
    return list(topo.devices)


@pytest.fixture(autouse=True)
def _production_precision():
    """conftest pins ``jax_default_matmul_precision=highest`` for the
    f64-reference suites; the precision is captured INTO a kernel's
    dots at trace time, and Mosaic rejects an fp32 contract on bf16
    operands ("Bad lhs type").  Production leaves the default, so does
    this file."""
    with jax.default_matmul_precision("default"):
        yield


@pytest.fixture(autouse=True)
def _clean_registry():
    kreg.reset_dispatch_counts()
    yield
    for name in kreg.kernels():
        kreg.set_mode(name, None)
    kreg.reset_dispatch_counts()


def _compile_on(dev, fn, *args):
    """AOT-compile ``fn`` for the single topology device ``dev`` from
    avals alone; returns the compiled HLO text."""
    sh = jax.sharding.SingleDeviceSharding(dev)
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sh), args)
    return jax.jit(fn).lower(*avals).compile().as_text()


def _kernel_cases():
    """name -> (fn through the real dispatch site, args): one small
    ALIGNED shape per registered kernel."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bhsd
    from paddle_tpu.ops.pallas.opt_apply import pack_hyper
    from paddle_tpu.ops.pallas.segment_sum import merge_segments
    f32, i8, i32 = np.float32, np.int8, np.int32
    n = 1 << 16
    qkv = [np.zeros((2, 4, 1024, 128), jnp.bfloat16)] * 3
    g, d, bs, nb, m = 4, 128, 16, 33, 8
    return {
        "flash_attention": (
            lambda q, k, v: jax.grad(
                lambda q_: flash_attention_bhsd(
                    q_, k, v, causal=True).astype(jnp.float32).sum())(q),
            qkv),
        "opt_apply": (
            lambda p, gr, m_, v_, h: kreg.dispatch(
                "opt_apply", "adam", p, gr, (m_, v_), h),
            [np.zeros(n, f32)] * 4 + [pack_hyper("adam", lr=1e-3, t=3)]),
        "int8_matmul": (
            lambda x, w, s: kreg.dispatch(
                "int8_matmul", x, w, s, x_scale=np.float32(0.02),
                compute_dtype=jnp.float32),
            [np.zeros((256, 1024), i8), np.zeros((1024, 1024), i8),
             np.zeros(1024, f32)]),
        "int8_kv_attention": (
            lambda *a: kreg.dispatch("int8_kv_attention", *a, g),
            [np.zeros((4, 1, 2 * g, d), f32),
             np.zeros((nb, bs, g, d), i8), np.zeros((nb, bs, g, d), i8),
             np.zeros((nb, bs), f32), np.zeros((nb, bs), f32),
             np.zeros((4, m), i32), np.zeros((4, 1), i32)]),
        # the decode kernel at both serving cells' shapes (PERF.md §4):
        # 128 slots x 64 blocks and 32 slots x 256 blocks of 16 over
        # ONE [8192, 16, 8, 128] bf16 pool pair, 32/8 heads x 128
        "paged_attention": (
            lambda kp, vp, *cells: [
                kreg.dispatch("paged_attention", q, kp, vp, None, None,
                              tbl, pos, 8)
                for q, tbl, pos in zip(cells[0::3], cells[1::3],
                                       cells[2::3])],
            [np.zeros((8192, 16, 8, 128), jnp.bfloat16)] * 2 + [
                a for b, mt in ((128, 64), (32, 256)) for a in (
                    np.zeros((b, 1, 32, 128), jnp.bfloat16),
                    np.zeros((b, mt), i32), np.zeros((b, 1), i32))]),
        # the same kernel at head 64 (PR 33: the lfm2 cell, 256 slots x
        # 112 blocks of 16): a pool of (16, 512) pages kept 3-D
        "paged_attention@head64": (
            lambda kp, vp, q, tbl, pos: kreg.dispatch(
                "paged_attention", q, kp, vp, None, None, tbl, pos, 8),
            [np.zeros((8192, 16, 512), jnp.bfloat16)] * 2 + [
                np.zeros((256, 1, 32, 64), jnp.bfloat16),
                np.zeros((256, 112), i32), np.zeros((256, 1), i32)]),
        # the latent decode kernel at 128 query heads over one
        # pool of 640-lane rows, V its first 512 lanes (the Pangu Ultra
        # MoE cell: 128 slots x 256 blocks of 16), and at 32 heads (the
        # Kimi-Linear cell: 128 slots x 192 blocks of 16)
        **{f"paged_latent_attention@latent{h}": (
            lambda pool, q, tbl, pos: kreg.dispatch(
                "paged_latent_attention", q, pool, tbl, pos, 512,
                192 ** -0.5),
            [np.zeros((128 * m + 1, 16, 1, 640), jnp.bfloat16),
             np.zeros((128, 1, h, 640), jnp.bfloat16),
             np.zeros((128, m), i32), np.zeros((128, 1), i32)])
           for h, m in ((128, 256), (32, 192))},
        "segment_sum": (
            lambda gr, inv: kreg.dispatch("segment_sum", gr, inv,
                                          num_segments=256),
            [np.zeros((1024, 128), f32), np.zeros(1024, i32)]),
        "segment_sum_sorted": (
            # host-side sort + bounds: the indices are trace constants
            lambda gr: merge_segments(
                gr, np.arange(1024, dtype=np.int64) * 8, 8192),
            [np.zeros((1024, 128), f32)]),
        "pull_dequant": (
            lambda c, s: kreg.dispatch("pull_dequant", c, s),
            [np.zeros((512, 128), i8), np.zeros(512, f32)]),
    }


def test_every_registered_kernel_has_a_case():
    assert sorted({n.split("@")[0] for n in _kernel_cases()}) \
        == sorted(kreg.kernels())


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e_through_dispatch(topo_devices, name):
    fn, args = _kernel_cases()[name]
    name = name.split("@")[0]           # a second shape of one kernel
    mesh_mod.init_mesh({"dp": -1}, devices=topo_devices[:1])
    assert mesh_mod.target_platform() == "tpu"
    hlo = _compile_on(topo_devices[0], fn, *args)
    counts = kreg.dispatch_counts(name)
    assert "fallback" not in counts, counts
    if kreg.kernels()[name].tpu_default == "xla_ref":
        # parked behind its reference on TPU (int8_kv_attention: the
        # pool layout fights Mosaic's (8, 128) block rule) — the
        # default route must compile, and must not be the kernel
        assert set(counts) == {"xla_ref"}, counts
        assert "tpu_custom_call" not in hlo
    else:
        assert set(counts) == {"pallas"}, counts
        assert "tpu_custom_call" in hlo


def _decoder_step(devices, degrees, zero_stage=0, batch=4, seq=1024):
    """A small decoder ``DistributedTrainStep`` (scan + remat, bf16
    AMP, AdamW) compiled from avals for ``devices``; flash is selected
    by the real eligibility gate (seq >= 1024, head_dim 128, TPU
    target)."""
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.fleet.dist_step import DistributedTrainStep
    from paddle_tpu.framework.core import abstract_init
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny

    mesh = mesh_mod.init_mesh(degrees, devices=devices)
    cfg = llama_tiny(vocab_size=1024, hidden_size=512,
                     intermediate_size=1024, num_hidden_layers=2,
                     num_attention_heads=4, num_key_value_heads=4,
                     max_position_embeddings=seq, scan_layers=True,
                     remat=True,
                     pp_num_microbatches=2 if degrees.get("pp") else 1)
    paddle.seed(0)
    with abstract_init():
        lm = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=lm.parameters())
    strategy = DistributedStrategy()
    strategy.amp = True
    strategy.amp_configs = {"dtype": "bfloat16"}
    if zero_stage:
        strategy.sharding = True
        strategy.sharding_configs = {"stage": zero_stage}

    def loss_fn(ids, labels):
        loss, _ = lm(ids, labels=labels)
        return loss

    step = DistributedTrainStep(lm, loss_fn, opt, strategy, mesh=mesh)
    ids = paddle.to_tensor(np.zeros((batch, seq), np.int32))
    return step.compile_abstract(ids, ids).as_text()


@pytest.mark.parametrize("n,degrees,zero", [
    (1, {"dp": -1}, 0),
    (4, {"fsdp": 4}, 2),
    (4, {"tp": 2, "fsdp": 2}, 2),
    (4, {"pp": 2, "tp": 2}, 0),
], ids=["1chip", "fsdp4_zero2", "tp2_fsdp2", "pp2_tp2"])
def test_decoder_step_with_flash_compiles_for_v5e(topo_devices, n,
                                                  degrees, zero):
    hlo = _decoder_step(topo_devices[:n], degrees, zero_stage=zero)
    assert "tpu_custom_call" in hlo
    assert set(kreg.dispatch_counts("flash_attention")) == {"pallas"}
