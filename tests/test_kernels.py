"""Per-kernel shape/dtype sweeps asserting allclose vs the ref.py oracles
(interpret mode on CPU, per the kernel checklist)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_update import fused_elastic_nag_update

KEY = jax.random.PRNGKey(7)


# ---------------------------------------------------------------------------
# fused elastic + NAG update
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(128,), (1000,), (33, 65), (4, 7, 130), (1,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_update_matches_ref(shape, dtype):
    ks = jax.random.split(KEY, 4)
    t = jax.random.normal(ks[0], shape, dtype)
    p = jax.random.normal(ks[1], shape, dtype)
    v = jax.random.normal(ks[2], shape, jnp.float32)
    g = jax.random.normal(ks[3], shape, jnp.float32)
    t2, v2 = fused_elastic_nag_update(t, p, v, g, 0.5, eta=0.01, mu=0.9,
                                      block=256, interpret=True)
    tr_, vr_ = ref.fused_elastic_nag_update(t, p, v, g, coef_gate=0.5, eta=0.01, mu=0.9)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(t2, np.float32), np.asarray(tr_, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(vr_), rtol=1e-6, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 2000), coef=st.floats(0.0, 1.0), eta=st.floats(0.0, 0.1),
       mu=st.floats(0.0, 0.99), seed=st.integers(0, 100))
def test_fused_update_property_sweep(n, coef, eta, mu, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    t, p, v, g = (jax.random.normal(k, (n,)) for k in ks)
    t2, v2 = fused_elastic_nag_update(t, p, v, g, coef, eta=eta, mu=mu,
                                      block=512, interpret=True)
    tr_, vr_ = ref.fused_elastic_nag_update(t, p, v, g, coef_gate=coef, eta=eta, mu=mu)
    np.testing.assert_allclose(np.asarray(t2), np.asarray(tr_), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(vr_), rtol=1e-5, atol=1e-6)


def test_fused_update_gate_zero_is_pure_nag():
    ks = jax.random.split(KEY, 4)
    t, p, v, g = (jax.random.normal(k, (300,)) for k in ks)
    t2, v2 = fused_elastic_nag_update(t, p, v, g, 0.0, eta=0.01, mu=0.9,
                                      block=128, interpret=True)
    v_ref = 0.9 * v - 0.01 * g
    t_ref = t - 0.01 * g + 0.9 * v_ref
    np.testing.assert_allclose(np.asarray(t2), np.asarray(t_ref), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(v_ref), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def make_qkv(B, H, Hkv, Sq, Skv, hd, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, H, Sq, hd), dtype),
            jax.random.normal(ks[1], (B, Hkv, Skv, hd), dtype),
            jax.random.normal(ks[2], (B, Hkv, Skv, hd), dtype))


def ref_bhsd(q, k, v, **kw):
    o = ref.attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                      jnp.swapaxes(v, 1, 2), **kw)
    return jnp.swapaxes(o, 1, 2)


@pytest.mark.parametrize("B,H,Hkv,S,hd", [
    (1, 2, 2, 64, 16), (2, 4, 2, 128, 32), (1, 8, 1, 96, 64), (2, 4, 4, 33, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_causal_sweep(B, H, Hkv, S, hd, dtype):
    q, k, v = make_qkv(B, H, Hkv, S, S, hd, dtype)
    o = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    orf = ref_bhsd(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(orf, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [1, 7, 33, 100])
def test_flash_sliding_window(window):
    q, k, v = make_qkv(1, 2, 2, 100, 100, 16)
    o = flash_attention(q, k, v, window=window, block_q=32, block_k=32, interpret=True)
    orf = ref_bhsd(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("softcap", [10.0, 50.0])
def test_flash_softcap(softcap):
    q, k, v = make_qkv(1, 4, 2, 64, 64, 32, seed=3)
    o = flash_attention(q, k, v, softcap=softcap, block_q=32, block_k=32, interpret=True)
    orf = ref_bhsd(q, k, v, causal=True, logit_softcap=softcap)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), rtol=2e-5, atol=2e-5)


def test_flash_decode_q1_with_kvlen():
    """Decode step: Sq=1, ring-buffer style valid length."""
    q, k, v = make_qkv(2, 4, 2, 1, 256, 32, seed=5)
    for kvlen in (1, 100, 256):
        o = flash_attention(q, k, v, jnp.int32(kvlen), causal=False,
                            block_q=8, block_k=64, interpret=True)
        orf = ref_bhsd(q, k, v, causal=False, kv_len=kvlen)
        np.testing.assert_allclose(np.asarray(o), np.asarray(orf), rtol=3e-5, atol=3e-5)


def test_flash_q_offset_matches_suffix_of_full():
    """Lowering decode with q_offset: rows [off, off+Sq) of full attention."""
    B, H, S, hd = 1, 2, 64, 16
    q, k, v = make_qkv(B, H, H, S, S, hd, seed=8)
    off = 48
    o = flash_attention(q[:, :, off:], k, v, q_offset=off, causal=True,
                        block_q=8, block_k=32, interpret=True)
    full = ref_bhsd(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(full[:, :, off:]),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 1000),
       S=st.sampled_from([17, 64, 130]),
       hd=st.sampled_from([8, 32]),
       bq=st.sampled_from([8, 16]), bk=st.sampled_from([16, 64]))
def test_flash_property_sweep(seed, S, hd, bq, bk):
    q, k, v = make_qkv(1, 2, 1, S, S, hd, seed=seed)
    o = flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
    orf = ref_bhsd(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), rtol=3e-5, atol=3e-5)


def test_ops_dispatch_ref_path_matches_kernel():
    from repro.kernels import ops
    q, k, v = make_qkv(1, 2, 2, 64, 64, 16)
    a = ops.flash_attention(q, k, v, use_kernel=False)
    b = ops.flash_attention(q, k, v, use_kernel=True, interpret=True,
                            block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# causal training kernel (forward with log-sum-exp, dq and dk/dv backward)
# ---------------------------------------------------------------------------

def _vmap_over_workers(f):
    """f over a leading worker dim of 2, as the sim engine stacks replicas."""
    def g(q, k, v):
        rep = lambda t: jnp.stack([t, 0.5 * t[:, ::-1]])
        return jax.vmap(f)(rep(q), rep(k), rep(v))[1]
    return g


@pytest.mark.parametrize("H,Hkv,S,bq,bk,wrap", [
    (4, 2, 128, 128, 128, "plain"),          # GQA, one block
    (4, 2, 384, 128, 128, "vmap+remat"),     # GQA, 3x3 blocks: causal skipping
    (2, 2, 256, 128, 128, "vmap"),           # MHA
    (4, 2, 512, 128, 256, "remat"),          # block_q < block_k
    (2, 2, 512, 256, 128, "plain"),          # MHA, block_q > block_k
    (6, 1, 2048, 1024, 1024, "plain"),       # MQA, the 6 heads in two groups of 3
])
def test_causal_train_kernel_matches_chunked(H, Hkv, S, bq, bk, wrap):
    """Output and d/dq, d/dk, d/dv of the training kernel (interpret mode,
    f32) against chunked_attention, under the transforms the sim step uses."""
    from repro.kernels.flash_attention import causal_attention
    from repro.models.attention import chunked_attention
    B, hd = 1, 128
    ks = jax.random.split(jax.random.PRNGKey(S + H), 4)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, Hkv, hd))
    v = jax.random.normal(ks[2], (B, S, Hkv, hd))
    ct = jax.random.normal(ks[3], (B, S, H, hd))
    kern = lambda q, k, v: causal_attention(q, k, v, block_q=bq, block_k=bk,
                                            interpret=True)
    chunk = lambda q, k, v: chunked_attention(q, k, v, causal=True, chunk=S)
    outs = []
    for f in (kern, chunk):
        if "remat" in wrap:
            f = jax.checkpoint(f)
        if "vmap" in wrap:
            f = _vmap_over_workers(f)
        o, vjp = jax.vjp(f, q, k, v)
        outs.append((o,) + vjp(ct))
    for a, b in zip(*outs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


def test_causal_train_kernel_refuses_blocks_that_do_not_tile():
    from repro.kernels.flash_attention import causal_attention
    q = jnp.zeros((1, 192, 2, 128))
    with pytest.raises(ValueError):
        causal_attention(q, q, q, block_q=128, block_k=128, interpret=True)
