"""Unit tests for model building blocks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.common.config import ModelConfig, MoEConfig
from repro.kernels import ref
from repro.models import moe as moe_mod
from repro.models.attention import chunked_attention
from repro.models.common import apply_rope, rmsnorm, softcap
from repro.models.ssm import causal_conv, causal_conv_step, gla_chunked, gla_step

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# chunked (online-softmax) attention vs naive oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Sq,Skv,chunk", [(16, 16, 4), (16, 16, 16), (1, 64, 8),
                                          (33, 33, 7), (8, 64, 64)])
def test_chunked_attention_matches_naive(Sq, Skv, chunk):
    B, H, Hkv, hd = 2, 4, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd))
    k = jax.random.normal(ks[1], (B, Skv, Hkv, hd))
    v = jax.random.normal(ks[2], (B, Skv, Hkv, hd))
    off = Skv - Sq
    o = chunked_attention(q, k, v, causal=True, q_offset=off, chunk=chunk)
    orf = ref.attention(q, k, v, causal=True, q_offset=off)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(window=st.integers(1, 40), softcap_v=st.sampled_from([0.0, 30.0]),
       seed=st.integers(0, 100))
def test_chunked_attention_window_softcap_property(window, softcap_v, seed):
    B, S, H, hd = 1, 32, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, hd)) for kk in ks)
    o = chunked_attention(q, k, v, causal=True, window=window,
                          logit_softcap=softcap_v, chunk=8)
    orf = ref.attention(q, k, v, causal=True, window=window, logit_softcap=softcap_v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), rtol=3e-5, atol=3e-5)


def test_chunked_attention_kv_len_mask():
    B, S, H, hd = 1, 1, 2, 8
    q = jax.random.normal(KEY, (B, S, H, hd))
    k = jax.random.normal(KEY, (B, 64, H, hd))
    v = jax.random.normal(KEY, (B, 64, H, hd))
    o1 = chunked_attention(q, k, v, causal=False, kv_len=10, chunk=16)
    o2 = chunked_attention(q, k[:, :10], v[:, :10], causal=False, chunk=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# RoPE / norms
# ---------------------------------------------------------------------------

def test_rope_preserves_norm_and_relative_positions():
    x = jax.random.normal(KEY, (1, 8, 2, 16))
    pos = jnp.arange(8)
    y = apply_rope(x, pos, 10000.0)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    # dot products depend only on relative position
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 16))
    def score(pq, pk):
        qq = apply_rope(q, jnp.array([pq]), 1e4)
        kk = apply_rope(k, jnp.array([pk]), 1e4)
        return float(jnp.sum(qq * kk))
    assert np.isclose(score(3, 1), score(10, 8), rtol=1e-4)


def test_softcap_bounds():
    x = jnp.linspace(-1e4, 1e4, 101)
    y = softcap(x, 30.0)
    assert float(jnp.max(jnp.abs(y))) <= 30.0
    np.testing.assert_allclose(np.asarray(softcap(jnp.array([0.1]), 50.0)),
                               [0.1], rtol=1e-4)


def test_rmsnorm_unit_scale():
    w = jnp.ones((16,))
    x = 100.0 * jax.random.normal(KEY, (4, 16))
    y = rmsnorm(w, x)
    rms = np.sqrt((np.asarray(y, np.float64) ** 2).mean(-1))
    np.testing.assert_allclose(rms, 1.0, rtol=1e-3)


# ---------------------------------------------------------------------------
# GLA core (mamba2/mLSTM substrate)
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), chunk=st.sampled_from([4, 8, 32]),
       S=st.sampled_from([8, 32, 64]))
def test_gla_chunked_matches_stepwise(seed, chunk, S):
    if S % chunk:
        chunk = S
    B, H, dk, dv = 1, 2, 4, 6
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, S, H, dk))
    k = jax.random.normal(ks[1], (B, S, H, dk))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = -jnp.abs(jax.random.normal(ks[3], (B, S, H))) * 0.5
    y_c, s_c = gla_chunked(q, k, v, g, chunk=chunk)
    state = jnp.zeros((B, H, dk, dv))
    ys = []
    for t in range(S):
        y, state = gla_step(q[:, t], k[:, t], v[:, t], g[:, t], state)
        ys.append(y)
    y_s = jnp.stack(ys, 1)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_s), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(state), rtol=2e-4, atol=2e-5)


def test_causal_conv_step_matches_full():
    cw, C, S, B = 4, 6, 12, 2
    w = jax.random.normal(KEY, (cw, C)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, C))
    full = causal_conv(w, x)
    buf = jnp.zeros((B, cw - 1, C))
    outs = []
    for t in range(S):
        y, buf = causal_conv_step(w, buf, x[:, t])
        outs.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# MoE dispatch
# ---------------------------------------------------------------------------

def _moe_cfg(E=4, k=2, cf=8.0):
    return ModelConfig(name="m", arch_type="moe", num_layers=1, d_model=16,
                       num_heads=2, num_kv_heads=2, d_ff=32, vocab_size=64,
                       activation="swiglu",
                       moe=MoEConfig(num_experts=E, top_k=k, num_shared_experts=1,
                                     d_ff_expert=32, capacity_factor=cf))


def test_moe_matches_dense_oracle_at_high_capacity():
    """Capacity dispatch with cf high enough == dense weighted expert sum."""
    cfg = _moe_cfg()
    p, _ = moe_mod.init_moe(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 16))
    y, aux = moe_mod.moe_forward(p, x, cfg)

    # dense oracle: run all experts on all tokens, combine with router weights
    xt = x.reshape(-1, 16)
    probs, w, ids = moe_mod._route(xt @ p["router"], cfg.moe.top_k)
    up = jnp.einsum("td,edf->tef", xt, p["w_up"])
    gate = jnp.einsum("td,edf->tef", xt, p["w_gate"])
    out_all = jnp.einsum("tef,efd->ted", jax.nn.silu(gate) * up, p["w_down"])
    dense = jnp.zeros_like(xt)
    for slot in range(cfg.moe.top_k):
        dense = dense + w[:, slot, None] * jnp.take_along_axis(
            out_all, ids[:, slot, None, None].repeat(16, -1), 1)[:, 0]
    from repro.models.mlp import ffn_forward
    dense = dense + ffn_forward(p["shared"], xt, "swiglu")
    np.testing.assert_allclose(np.asarray(y.reshape(-1, 16)), np.asarray(dense),
                               rtol=2e-4, atol=2e-5)
    assert float(aux) > 0


def test_moe_capacity_drops_tokens():
    cfg = _moe_cfg(cf=0.25)
    p, _ = moe_mod.init_moe(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 16))
    y, _ = moe_mod.moe_forward(p, x, cfg)
    assert bool(jnp.isfinite(y).all())


def test_router_stats_load_sums_to_one():
    cfg = _moe_cfg()
    p, _ = moe_mod.init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (2, 32, 16))
    stats = moe_mod.router_stats(p, x, cfg)
    np.testing.assert_allclose(float(stats["expert_load"].sum()), 1.0, rtol=1e-5)


# ---------------------------------------------------------------------------
# which attention calls take the training flash kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw,takes", [
    (dict(), True),
    (dict(backend="cpu"), False),
    (dict(window=jnp.zeros((), jnp.int32)), False),   # a traced window
    (dict(window=4096), False),
    (dict(softcap=50.0), False),
    (dict(head_dim=64), False),
    (dict(seq_q=1, seq_kv=1024), False),
    (dict(seq_q=1000, seq_kv=1000), False),
    (dict(seq_q=128, seq_kv=128), True),
    (dict(seq_q=4096, seq_kv=4096, head_dim=256), True),
    (dict(devices=4), False),                         # a program over four chips
    (dict(groups=48), True),                          # granite_20b: 48 heads, 1 kv head
    (dict(groups=1, head_dim=1024), False),           # one head's block is too large
])
def test_flash_dispatch_rule(kw, takes):
    from repro.kernels import flash_attention as flash
    args = dict(backend="tpu", devices=1, seq_q=1024, seq_kv=1024, groups=4,
                head_dim=128, window=0, softcap=0.0)
    assert flash.fits(**{**args, **kw}) is takes


def _benchmark_granite_cut1():
    import json
    from pathlib import Path
    import sys
    chip = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
    sys.path.insert(0, str(chip))
    import harness
    return harness.model_config(json.loads((chip / "configs" / "granite_3_8b.cut1.json")
                                           .read_text()))


# config -> what the TPU would run for each traced self-attention call of the
# training forward at S=1024: True the kernel, False chunked_attention; an
# empty set means no gqa_forward call (MLA, cross-attention only, no attention)
TAKES_KERNEL = {
    "tinyllama_1_1b": {False},          # head_dim 64
    "deepseek_v2_lite_16b": set(),      # MLA
    "xlstm_125m": set(),                # no attention
    "granite_20b": {True},
    "grok_1_314b": {True},
    "granite_3_8b": {True},
    "musicgen_large": {False},          # head_dim 64
    "gemma2_9b": {False},               # per-layer (traced) windows, softcap
    "llama_3_2_vision_11b": {True},     # self-attention; cross-attention stays jnp
    "zamba2_2_7b": {False},             # head_dim 80
    "granite_3_8b.cut1": {True},        # the benchmark's configuration
}


@pytest.mark.parametrize("name", sorted(TAKES_KERNEL))
def test_which_training_attention_takes_the_kernel(name, monkeypatch):
    """Trace each full configuration's training forward (shapes only) with
    the dispatch rule asked as if on one TPU chip, and record its answers."""
    from repro.configs import ARCH_IDS, get_config
    from repro.kernels import flash_attention as flash
    from repro.models import transformer as tr
    assert set(ARCH_IDS) | {"granite_3_8b.cut1"} == set(TAKES_KERNEL)
    cfg = _benchmark_granite_cut1() if name == "granite_3_8b.cut1" else get_config(name)
    seen, fits = [], flash.fits

    def spy(**kw):
        seen.append(fits(**{**kw, "backend": "tpu", "devices": 1}))
        return False                     # the CPU still runs chunked_attention
    monkeypatch.setattr(flash, "fits", spy)
    params, _ = tr.abstract_lm(cfg)
    S = 1024
    if cfg.audio is not None:
        tokens = jax.ShapeDtypeStruct((1, cfg.audio.num_codebooks, S), jnp.int32)
        cond = jax.ShapeDtypeStruct((1, cfg.audio.num_cond_tokens, cfg.d_model), jnp.float32)
    else:
        tokens = jax.ShapeDtypeStruct((1, S), jnp.int32)
        cond = (jax.ShapeDtypeStruct((1, cfg.vlm.num_image_tokens, cfg.vlm.image_embed_dim),
                                     jnp.float32) if cfg.vlm is not None else None)
    jax.eval_shape(lambda p, t, c: tr.forward(p, cfg, t, c), params, tokens, cond)
    assert set(seen) == TAKES_KERNEL[name]


def test_gqa_forward_off_the_tpu_runs_chunked_attention():
    from repro.models.attention import gqa_forward, init_gqa
    cfg = ModelConfig(name="t", arch_type="dense", num_layers=1, d_model=256,
                      num_heads=2, num_kv_heads=1, d_ff=512, vocab_size=64)
    p, _ = init_gqa(KEY, cfg)
    x = jnp.ones((1, 128, 256))
    jaxpr = str(jax.make_jaxpr(lambda p, x: gqa_forward(p, x, cfg)[0])(p, x))
    assert jax.default_backend() != "tpu"
    assert "pallas_call" not in jaxpr and "scan" in jaxpr
