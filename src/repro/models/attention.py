"""Attention: GQA, sliding-window, softcap, cross-attention, and MLA.

The core is :func:`chunked_attention` — an online-softmax scan over KV blocks
(the pure-jnp analogue of the Pallas flash kernel in repro/kernels; the
kernels' ref.py delegates here). Peak memory is O(S * chunk), never O(S^2),
so dry-run memory analysis reflects production behavior (DESIGN.md §7).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import MLAConfig, ModelConfig
from repro.kernels import flash_attention as flash
from repro.models.common import apply_rope, dense_init, split_tree, zeros_init

PyTree = Any

NEG_INF = -1e30


def chunked_attention(q, k, v, *, causal: bool = True, window=0, logit_softcap: float = 0.0,
                      q_offset=0, kv_len: Optional[jax.Array] = None,
                      kv_start: Optional[jax.Array] = None, chunk: int = 1024):
    """Online-softmax attention.

    q: [B, Sq, H, hd]; k, v: [B, Skv, Hkv, hd] with H % Hkv == 0.
    window: 0 = full; >0 = attend to keys with q_pos - k_pos in [0, window).
            May be a traced scalar (per-layer local/global in one scan).
    kv_len: optional scalar count of valid cache entries (decode).
    kv_start: optional [B] first valid cache position per batch row — the
              continuous-batching slot boundary: a request admitted into a
              recycled slot at cache position p attends only to kv_pos >= p,
              so the previous occupant's K/V rows are masked out exactly
              (repro.serve). None (the default) traces the original program.
    q_offset: absolute position of q[0] (decode/prefill continuation).
    """
    B, Sq, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]                     # may differ from hd (MLA latent values)
    G = H // Hkv
    qf = q.reshape(B, Sq, Hkv, G, hd).astype(jnp.float32)
    scale = hd ** -0.5
    q_pos = q_offset + jnp.arange(Sq)

    nchunks = max(1, (Skv + chunk - 1) // chunk)
    pad = nchunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, nchunks, chunk, Hkv, hd)
    vc = v.reshape(B, nchunks, chunk, Hkv, dv)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, cidx = xs
        kv_pos = cidx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kb.astype(jnp.float32)) * scale
        if logit_softcap:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        mask = jnp.ones((Sq, chunk), bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        mask &= (q_pos[:, None] - kv_pos[None, :]) < jnp.where(
            jnp.asarray(window) > 0, jnp.asarray(window), jnp.iinfo(jnp.int32).max)
        mask &= kv_pos[None, :] < (Skv if kv_len is None else kv_len)
        if kv_start is None:
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        else:
            # per-row lower bound: [B, Sq, chunk], aligned as [B, 1, 1, Sq, chunk]
            bmask = mask[None, :, :] & (
                kv_pos[None, None, :] >= jnp.asarray(kv_start)[:, None, None])
            s = jnp.where(bmask[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, dv), jnp.float32)
    # flash-attention backward: recompute each chunk's scores instead of
    # saving [nchunks, B, H, Sq, chunk] f32 for the whole sequence
    # (EXPERIMENTS.md §Perf iteration 2)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.arange(nchunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA self-attention module
# ---------------------------------------------------------------------------

def init_gqa(key, cfg: ModelConfig, dtype=jnp.float32) -> Tuple[PyTree, PyTree]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    return split_tree({
        "wq": dense_init(ks[0], (d, H, hd), ("embed", "heads", None), dtype),
        "wk": dense_init(ks[1], (d, Hkv, hd), ("embed", "kv_heads", None), dtype),
        "wv": dense_init(ks[2], (d, Hkv, hd), ("embed", "kv_heads", None), dtype),
        "wo": dense_init(ks[3], (H, hd, d), ("heads", None, "embed"), dtype, fan_in=H * hd),
    })


def gqa_qkv(p, x, positions, theta):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    return q, k, v


def _program_devices() -> int:
    """Devices the program being traced may be partitioned over: the ambient
    mesh's (as the dry-run sets it), else every device of the run."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh.size if mesh.axis_names else jax.device_count()


@jax.named_scope("attention")
def gqa_forward(p, x, cfg: ModelConfig, *, window=0, positions=None, chunk: int = 1024):
    B, S, _ = x.shape
    positions = jnp.arange(S) if positions is None else positions
    q, k, v = gqa_qkv(p, x, positions, cfg.rope_theta)
    if flash.fits(backend=jax.default_backend(), devices=_program_devices(), seq_q=S,
                  seq_kv=S, groups=q.shape[2] // k.shape[2], head_dim=q.shape[-1],
                  window=window, softcap=cfg.attn_logit_softcap):
        o = flash.causal_attention(q, k, v)
    else:
        o = chunked_attention(q, k, v, causal=True, window=window,
                              logit_softcap=cfg.attn_logit_softcap, chunk=min(chunk, S))
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype)), (k, v)


def gqa_decode(p, x, cache_k, cache_v, pos, cfg: ModelConfig, *, window=0,
               kv_start=None, chunk: int = 1024):
    """x: [B, 1, d]; cache_[kv]: [B, Smax, Hkv, hd]; pos: scalar next index.
    kv_start: optional [B] per-slot first valid cache row (see
    :func:`chunked_attention`). Returns (out, new_k_cache, new_v_cache)."""
    positions = pos + jnp.zeros((1,), jnp.int32)
    q, k, v = gqa_qkv(p, x, positions, cfg.rope_theta)
    ck = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), pos, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), pos, axis=1)
    o = chunked_attention(q, ck, cv, causal=True, window=window,
                          logit_softcap=cfg.attn_logit_softcap,
                          q_offset=pos, kv_len=pos + 1, kv_start=kv_start, chunk=chunk)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype)), ck, cv


# ---------------------------------------------------------------------------
# Cross-attention (VLM image layers / MusicGen conditioning)
# ---------------------------------------------------------------------------

def init_cross_attn(key, cfg: ModelConfig, kv_dim: int, dtype=jnp.float32):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 5)
    return split_tree({
        "wq": dense_init(ks[0], (d, H, hd), ("embed", "heads", None), dtype),
        "wk": dense_init(ks[1], (kv_dim, Hkv, hd), ("embed", "kv_heads", None), dtype),
        "wv": dense_init(ks[2], (kv_dim, Hkv, hd), ("embed", "kv_heads", None), dtype),
        "wo": dense_init(ks[3], (H, hd, d), ("heads", None, "embed"), dtype, fan_in=H * hd),
        "gate": zeros_init((1,), (None,), dtype),   # tanh-gated residual (llama3.2-V)
    })


@jax.named_scope("attention")
def cross_attn_forward(p, x, cond, cfg: ModelConfig, chunk: int = 1024):
    """x: [B, S, d]; cond: [B, T, kv_dim] (stubbed modality embeddings)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dhk->bthk", cond.astype(x.dtype), p["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", cond.astype(x.dtype), p["wv"].astype(x.dtype))
    o = chunked_attention(q, k, v, causal=False, chunk=min(chunk, cond.shape[1]))
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    gate = jnp.tanh(p["gate"].astype(jnp.float32))[0].astype(y.dtype)
    return gate * y


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, dtype=jnp.float32):
    m: MLAConfig = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 6)
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    tree = {
        "wq": dense_init(ks[0], (d, H, qk_dim), ("embed", "heads", None), dtype),
        "kv_down": dense_init(ks[1], (d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", None), dtype),
        "k_up": dense_init(ks[2], (m.kv_lora_rank, H, m.qk_nope_head_dim), (None, "heads", None), dtype,
                           fan_in=m.kv_lora_rank),
        "v_up": dense_init(ks[3], (m.kv_lora_rank, H, m.v_head_dim), (None, "heads", None), dtype,
                           fan_in=m.kv_lora_rank),
        "wo": dense_init(ks[4], (H, m.v_head_dim, d), ("heads", None, "embed"), dtype,
                         fan_in=H * m.v_head_dim),
        "kv_norm": (jnp.ones((m.kv_lora_rank,), dtype), ("act_embed",)),
    }
    return split_tree(tree)


def _mla_qc(p, x, cfg: ModelConfig, positions):
    """Shared projections: q (nope+rope), latent cache entries (c_kv, k_rope)."""
    m = cfg.mla
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    down = jnp.einsum("bsd,dr->bsr", x, p["kv_down"].astype(x.dtype))
    c_kv, k_rope = down[..., :m.kv_lora_rank], down[..., m.kv_lora_rank:]
    from repro.models.common import rmsnorm
    c_kv = rmsnorm(p["kv_norm"], c_kv, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return q_nope, q_rope, c_kv, k_rope


@jax.named_scope("attention")
def mla_forward(p, x, cfg: ModelConfig, *, positions=None, chunk: int = 1024):
    """Training/prefill with the ABSORBED formulation: scores and values are
    computed against the compact latent c_kv, so no [B,S,H,hd] K/V are ever
    materialized — the same trick that makes the 500k decode cache 576/token."""
    m = cfg.mla
    B, S, _ = x.shape
    positions = jnp.arange(S) if positions is None else positions
    q_nope, q_rope, c_kv, k_rope = _mla_qc(p, x, cfg, positions)
    # absorb k_up into q: q_lat [B,S,H,r]
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, p["k_up"].astype(x.dtype))
    # attention with "keys" = [c_kv ; k_rope] and "queries" = [q_lat ; q_rope]
    qq = jnp.concatenate([q_lat, jnp.broadcast_to(q_rope, q_rope.shape)], axis=-1)
    kk = jnp.concatenate([c_kv, k_rope], axis=-1)[:, :, None, :]       # Hkv=1
    scale_fix = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5 / (qq.shape[-1] ** -0.5)
    o_lat = chunked_attention(qq * scale_fix, kk, c_kv[:, :, None, :], causal=True,
                              chunk=min(chunk, S))                      # [B,S,H,r]
    o = jnp.einsum("bshr,rhv->bshv", o_lat, p["v_up"].astype(x.dtype))
    out = jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(x.dtype))
    return out, (c_kv, k_rope)


def mla_decode(p, x, cache_c, cache_kr, pos, cfg: ModelConfig, *, kv_start=None,
               chunk: int = 2048):
    """cache_c: [B, Smax, r]; cache_kr: [B, Smax, rope_dim]."""
    positions = pos + jnp.zeros((1,), jnp.int32)
    q_nope, q_rope, c_kv, k_rope = _mla_qc(p, x, cfg, positions)
    cc = jax.lax.dynamic_update_slice_in_dim(cache_c, c_kv.astype(cache_c.dtype), pos, axis=1)
    ckr = jax.lax.dynamic_update_slice_in_dim(cache_kr, k_rope.astype(cache_kr.dtype), pos, axis=1)
    m = cfg.mla
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, p["k_up"].astype(x.dtype))
    qq = jnp.concatenate([q_lat, q_rope], axis=-1)
    kk = jnp.concatenate([cc, ckr], axis=-1)[:, :, None, :].astype(x.dtype)
    scale_fix = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5 / (qq.shape[-1] ** -0.5)
    o_lat = chunked_attention(qq * scale_fix, kk, cc[:, :, None, :].astype(x.dtype), causal=True,
                              q_offset=pos, kv_len=pos + 1, kv_start=kv_start, chunk=chunk)
    o = jnp.einsum("bshr,rhv->bshv", o_lat, p["v_up"].astype(x.dtype))
    return jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(x.dtype)), cc, ckr
