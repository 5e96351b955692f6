"""Plain reference of a dense decoder (Llama-style, as Granite-3.0 is built):
pre-norm residual blocks of grouped-query causal self-attention with rotary
positions and a SwiGLU feed-forward, then a final RMSNorm and the head: the
embedding's transpose where ``tie_embeddings`` is set, else a matrix of its own.

Rotary embedding rotates the two halves of each head vector (``x1, x2`` =
first and second half) by ``pos * theta ** (-2i / head_dim)``. Scores are
scaled by ``head_dim ** -0.5``; query head ``h`` reads key/value head
``h // (num_heads / num_kv_heads)``. No bias terms, no multipliers on the
embedding, attention, residual or logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from refs.plain import Init, cross_entropy, rmsnorm, stack_layers


def _head_dim(cfg):
    return cfg.get("head_dim") or cfg["d_model"] // cfg["num_heads"]


def init(cfg, key):
    """Seeded float32 weights in the stored layout (embed [1, V, d], one
    segment ``seg0_attn`` of stacked layers, final_norm, and lm_head [1, d, V]
    unless the embedding is tied)."""
    d, V, F = cfg["d_model"], cfg["vocab_size"], cfg["d_ff"]
    H, Hkv, hd = cfg["num_heads"], cfg["num_kv_heads"], _head_dim(cfg)
    rng = Init(key)

    def layer():
        return {"ln1": rng.const((d,), 1.0),
                "attn": {"wq": rng.normal((d, H, hd), d),
                         "wk": rng.normal((d, Hkv, hd), d),
                         "wv": rng.normal((d, Hkv, hd), d),
                         "wo": rng.normal((H, hd, d), H * hd)},
                "ln2": rng.const((d,), 1.0),
                "ffn": {"w_gate": rng.normal((d, F), d),
                        "w_up": rng.normal((d, F), d),
                        "w_down": rng.normal((F, d), F)}}

    params = {"embed": rng.normal((1, V, d), d, scale=0.5),
              "segments": {"seg0_attn": stack_layers(
                  [layer() for _ in range(cfg["num_layers"])])},
              "final_norm": rng.const((d,), 1.0)}
    if not cfg.get("tie_embeddings"):
        params["lm_head"] = rng.normal((1, d, V), d)
    return params


def _rope(x, theta):
    """x: [B, S, H, hd] rotated by position along S."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv          # [S, hd/2]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(p, x, cfg):
    B, S, _ = x.shape
    H, Hkv, hd = cfg["num_heads"], cfg["num_kv_heads"], _head_dim(cfg)
    q = _rope(jnp.einsum("bsd,dhk->bshk", x, p["wq"]), cfg["rope_theta"])
    k = _rope(jnp.einsum("bsd,dhk->bshk", x, p["wk"]), cfg["rope_theta"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = q.reshape(B, S, Hkv, H // Hkv, hd)
    scores = jnp.einsum("bqhgk,bshk->bhgqs", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhgqs,bshk->bqhgk", probs, v).reshape(B, S, H, hd)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def forward_flops(cfg, seq):
    """Forward operations per token of the whole model at sequence length
    ``seq``, by term (the rule is ``flops.py``'s): ``weights``, 2 x the
    matmul weights a token passes in every layer (q, k, v, o and the SwiGLU's
    three); ``attention``, q.k and p.v of every layer over the mean causal
    context; ``head``, the LM head, tied or not."""
    d, V, F = cfg["d_model"], cfg["vocab_size"], cfg["d_ff"]
    H, Hkv, hd = cfg["num_heads"], cfg["num_kv_heads"], _head_dim(cfg)
    L = cfg["num_layers"]
    ctx = (seq + 1) / 2
    weights = d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * F
    return {"weights": L * 2.0 * weights,
            "attention": L * 2.0 * 2 * H * hd * ctx,
            "head": 2.0 * d * V}


def _ffn(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def loss(params, cfg, tokens, labels):
    """Mean next-token cross-entropy of one replica on ``tokens`` [B, S]."""
    eps = cfg["norm_eps"]
    x = params["embed"][0][tokens]
    seg = params["segments"]["seg0_attn"]
    for j in range(cfg["num_layers"]):
        p = jax.tree.map(lambda t: t[j], seg)

        def block(x, p):
            x = x + _attention(p["attn"], rmsnorm(p["ln1"], x, eps), cfg)
            return x + _ffn(p["ffn"], rmsnorm(p["ln2"], x, eps))

        x = jax.checkpoint(block)(x, p)
    x = rmsnorm(params["final_norm"], x, eps)
    head = (params["embed"][0].T if cfg.get("tie_embeddings")
            else params["lm_head"][0])
    return cross_entropy(x, head, labels)
