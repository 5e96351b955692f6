"""The main-path Pallas kernels compile for a TPU v5e at real plane widths.

Nothing here runs on a chip: the TPU compiler builds each kernel for a
described (not attached) ``v5e:2x2`` topology, at the width of the
xlstm_125m flat plane. Interpret-mode tests cannot see what this catches —
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
