"""The main-path Pallas kernels compile for a TPU v5e at real plane widths.

Nothing here runs on a chip: the TPU compiler builds each kernel for a
described (not attached) ``v5e:2x2`` topology, at the width of the
xlstm_125m flat plane, the training flash attention kernels at the granite
cell's, granite_20b's and grok's attention shapes, and the dist train step
over one and four chips. Interpret-mode tests cannot see what this catches —
block shapes the chip's tiling refuses, VMEM overuse, a relayout copy of the
plane. The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one running this file loads
the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.common.config import ProtocolConfig
from repro.kernels import codec, fused_update, robust

KERNELS = ("fused_elastic_nag", "fused_nag", "robust_apply", "q8_encode",
           "q8_decode", "topk_encode", "topk_decode")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one:
    keep the persistent compilation cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def plane_n():
    """Elements in one replica row of the xlstm_125m flat plane."""
    from repro.common.flat import FlatSpec
    from repro.configs import get_config
    from repro.models import transformer as tr
    abstract, _ = tr.abstract_lm(get_config("xlstm_125m"))
    (n,) = FlatSpec.build(abstract).totals.values()
    return n


def _program(kernel: str, W: int, n: int, sharding):
    """(fn, abstract args) of one kernel on a [W, n] f32 plane."""
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    plane, row = sds((W, n)), sds((W,))
    cfg = ProtocolConfig(codec="topk")
    block = cfg.codec_block
    k = max(1, int(round(cfg.codec_topk_frac * block)))
    nb = -(-n // block)
    return {
        "fused_elastic_nag": (
            lambda t, p, v, g, c: fused_update.fused_flat_elastic_nag_update(
                t, p, v, g, c, 0.01, 0.9), (plane, plane, plane, plane, row)),
        "fused_nag": (
            lambda t, v, g: fused_update.fused_flat_nag_update(t, v, g, 0.01, 0.9),
            (plane, plane, plane)),
        "robust_apply": (
            lambda t, d, s, h: robust.robust_flat_apply(t, d, s, h),
            (plane, plane, row, row)),
        "q8_encode": (
            lambda b, s: codec.q8_encode(b, s, block=block),
            (plane, sds((W,), jnp.uint32))),
        "q8_decode": (
            lambda v, s: codec.q8_decode(v, s, n=n, block=block),
            (sds((W, nb * block), jnp.int8), sds((W, nb)))),
        "topk_encode": (
            lambda b, r: codec.topk_encode(b, r, k=k, block=block),
            (plane, plane)),
        "topk_decode": (
            lambda v, i: codec.topk_decode(v, i, n=n, k=k, block=block),
            (sds((W, nb * k)), sds((W, nb * k), jnp.int32))),
    }[kernel]


@pytest.mark.parametrize("W", [4, 1])
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(kernel, W, one_chip, plane_n):
    fn, args = _program(kernel, W, plane_n, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the kernel reads the plane where it lies: no plane-sized relayout copy
    assert compiled.memory_analysis().temp_size_in_bytes < plane_n * 4


# (W, B, S, H, Hkv): the granite cell's attention, granite_20b's (48 query heads
# on one kv head, taken in groups) and grok's (6 query heads per kv head) at 4k
FLASH_SHAPES = {"granite_3_8b.cut1": (2, 4, 1024, 32, 8),
                "granite_20b": (1, 1, 4096, 48, 1),
                "grok_1_314b": (1, 1, 4096, 48, 8)}


@pytest.mark.parametrize("shape", sorted(FLASH_SHAPES))
def test_flash_train_kernels_compile_for_v5e(shape, one_chip):
    """The training flash kernels (forward with log-sum-exp, dq, dk/dv), W
    replicas vmapped, heads of 128, f32, within their scoped VMEM."""
    from repro.kernels.flash_attention import causal_attention
    W, B, S, H, Hkv = FLASH_SHAPES[shape]
    hd = 128

    def sds(heads):
        return jax.ShapeDtypeStruct((W, B, S, heads, hd), jnp.float32, sharding=one_chip)

    def loss(q, k, v, ct):
        return jnp.sum(jax.vmap(causal_attention)(q, k, v) * ct)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        sds(H), sds(Hkv), sds(Hkv), sds(H)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in ("flash_fwd_lse", "flash_dq", "flash_dkv"):
        assert name in text


def _dist_train_step(devices):
    """The dist engine's gradient-mean train step compiled over ``devices``,
    one replica on each, under the ambient mesh as the dry-run compiles it:
    one dense layer with two 128-lane query heads on one kv head, S=256."""
    import numpy as np
    from jax.sharding import AxisType, Mesh
    from repro.common.config import (MeshConfig, ModelConfig, OptimizerConfig,
                                     TrainConfig)
    from repro.launch.specs import make_trainer
    n = len(devices)
    mesh = Mesh(np.array(devices).reshape(1, n, 1, 1), ("pod", "worker", "fsdp", "model"),
                axis_types=(AxisType.Auto,) * 4)
    cfg = ModelConfig(name="t", arch_type="dense", num_layers=1, d_model=256,
                      num_heads=2, num_kv_heads=1, d_ff=512, vocab_size=256)
    train_cfg = TrainConfig(protocol=ProtocolConfig(method="allreduce"),
                            optimizer=OptimizerConfig(name="nag", learning_rate=1e-3,
                                                      momentum=0.9))
    trainer = make_trainer(mesh, MeshConfig(data=n, model=1, workers_per_pod=n), cfg,
                           1, train_cfg)
    trainer.set_shape(2 * n, 256)
    with jax.set_mesh(mesh):
        lowered = trainer.jit_train_step().lower(
            trainer.state_shapes(), trainer.batch_shapes(),
            jax.ShapeDtypeStruct((), jnp.float32))
    return lowered.compile().as_text()


@pytest.mark.parametrize("chips", [1, 4])
def test_dist_step_attention_on_v5e(chips, topo, no_persistent_cache, monkeypatch):
    """Over one chip the dist step's attention takes the training kernels.
    Over four, where GSPMD partitions the step (the replicas' dim is sharded)
    and JAX refuses to lower a pallas_call it would have to partition, the
    step keeps chunked_attention and gathers nothing."""
    from repro.kernels import flash_attention as flash
    fits = flash.fits
    monkeypatch.setattr(flash, "fits", lambda **kw: fits(**{**kw, "backend": "tpu"}))
    text = _dist_train_step(topo.devices[:chips])
    kernels = [n for n in ("flash_fwd_lse", "flash_dq", "flash_dkv") if n in text]
    if chips == 1:
        assert len(kernels) == 3
    else:
        assert kernels == [] and "all-gather" not in text
