"""Pure-jnp oracles for the Pallas kernels (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fused_elastic_nag_update(theta, peer, v, g, *, coef_gate: float, eta: float, mu: float):
    """The paper's per-parameter hot loop (Alg. 5 lines 3/7/9, simultaneous):

        v'     = mu * v - eta * g
        theta' = theta - coef_gate * (theta - peer) - eta * g + mu * v'

    coef_gate = alpha * gate folds the participation gate into the moving rate.
    Returns (theta', v').
    """
    tf, pf = theta.astype(jnp.float32), peer.astype(jnp.float32)
    vf, gf = v.astype(jnp.float32), g.astype(jnp.float32)
    v_new = mu * vf - eta * gf
    theta_new = tf - coef_gate * (tf - pf) - eta * gf + mu * v_new
    return theta_new.astype(theta.dtype), v_new.astype(v.dtype)


def _per_replica(c, W: int) -> jnp.ndarray:
    """Scalar or [W] -> [W, 1] f32 column (broadcasts over the flat axis)."""
    return jnp.broadcast_to(jnp.asarray(c, jnp.float32).reshape(-1), (W,))[:, None]


def fused_flat_elastic_nag_update(theta, peer, v, g, coef, eta, mu):
    """Flat-plane oracle: same math as :func:`fused_elastic_nag_update` on
    [W, N] replica buffers with per-replica ``coef`` (scalar or [W]) and
    traced ``eta``/``mu``. Returns (theta', v')."""
    W = theta.shape[0]
    c = _per_replica(coef, W)
    tf, pf = theta.astype(jnp.float32), peer.astype(jnp.float32)
    vf, gf = v.astype(jnp.float32), g.astype(jnp.float32)
    v_new = mu * vf - eta * gf
    theta_new = tf - c * (tf - pf) - eta * gf + mu * v_new
    return theta_new.astype(theta.dtype), v_new.astype(v.dtype)


def fused_flat_nag_update(theta, v, g, eta, mu):
    """Flat-plane pure-NAG oracle (Alg. 5 lines 3 & 9, no communication)."""
    tf = theta.astype(jnp.float32)
    vf, gf = v.astype(jnp.float32), g.astype(jnp.float32)
    v_new = mu * vf - eta * gf
    theta_new = tf - eta * gf + mu * v_new
    return theta_new.astype(theta.dtype), v_new.astype(v.dtype)


def robust_flat_apply(theta, delta, scale, thr):
    """Robust-gossip displacement apply oracle (Pallas kernel in robust.py):
    theta + scale * delta, with delta coordinates above the per-row trim
    threshold zeroed (thr = +inf disables trimming)."""
    W = theta.shape[0]
    s, t = _per_replica(scale, W), _per_replica(thr, W)
    df = delta.astype(jnp.float32)
    keep = (jnp.abs(df) <= t).astype(jnp.float32)
    out = theta.astype(jnp.float32) + s * (df * keep)
    return out.astype(theta.dtype)


# ---------------------------------------------------------------------------
# Gossip-compression codec oracles (repro.comm; Pallas kernels in codec.py)
# ---------------------------------------------------------------------------

def stochastic_uniform(idx, seed):
    """Deterministic per-element uniform in [0, 1): murmur-style integer hash
    of (seed, element index). Both the Pallas codec kernels and these oracles
    draw rounding noise from THIS function, so kernel-vs-oracle parity is
    bit-exact and the sim / dist engines produce identical wire payloads from
    identical (round, worker) seeds. ``idx``: uint32 array of in-row element
    indices; ``seed``: uint32 scalar/array broadcastable against it."""
    x = jnp.asarray(idx, jnp.uint32) ^ jnp.asarray(seed, jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    # top 24 bits -> [0, 1) with full float32 resolution (through int32:
    # exact for 24-bit values, and Mosaic has no uint32 -> f32 cast)
    return ((x >> 8).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24)))


def _pad_to_blocks(x, block: int):
    """[W, N] -> ([W, nb, block] zero-padded, nb)."""
    W, n = x.shape
    nb = max(1, -(-n // block))
    pad = nb * block - n
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x.reshape(W, nb, block), nb


def q8_encode(buf, seeds, *, block: int):
    """Stochastic-rounding int8 quantization with per-block scales.

    buf: [W, N] float buffer (flat-plane bucket); seeds: [W] uint32 per-row
    rounding seeds. Returns (values int8 [W, nb*block], scales f32 [W, nb])
    where nb = ceil(N / block); the tail of the last block is zero-padded
    (padded lanes quantize to 0).
    """
    W, n = buf.shape
    x, nb = _pad_to_blocks(buf.astype(jnp.float32), block)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    # explicit multiply: XLA rewrites division-by-constant into a reciprocal
    # multiply in SOME lowerings (1-ulp divergence kernel-vs-oracle); an
    # explicit f32 multiply is the same everywhere
    scale = jnp.where(amax > 0, amax * jnp.float32(1.0 / 127.0), 1.0)
    idx = jnp.arange(nb * block, dtype=jnp.uint32).reshape(1, nb, block)
    u = stochastic_uniform(idx, seeds.astype(jnp.uint32)[:, None, None])
    q = jnp.clip(jnp.floor(x / scale + u), -127.0, 127.0)
    return q.astype(jnp.int8).reshape(W, nb * block), scale.reshape(W, nb)


def q8_decode(values, scales, n: int, *, block: int):
    """Inverse of :func:`q8_encode`: [W, nb*block] int8 + [W, nb] f32 scales
    -> [W, n] float32."""
    W = values.shape[0]
    nb = scales.shape[1]
    x = values.astype(jnp.float32).reshape(W, nb, block) * scales[..., None]
    return x.reshape(W, nb * block)[:, :n]


def topk_encode(buf, residual, *, k: int, block: int):
    """Per-block magnitude top-k with error feedback.

    Selects, within every ``block``-element block of ``acc = buf + residual``,
    the ``k`` entries of largest magnitude (ties -> lowest index, matching the
    kernel's iterative argmax). Returns (values f32 [W, nb*k],
    local block indices int32 [W, nb*k], residual' f32 [W, N]) with
    residual' = acc minus everything transmitted.
    """
    W, n = buf.shape
    acc = buf.astype(jnp.float32)
    if residual is not None:
        acc = acc + residual.astype(jnp.float32)
    accb, nb = _pad_to_blocks(acc, block)
    _, idx = jax.lax.top_k(jnp.abs(accb), k)                  # [W, nb, k]
    values = jnp.take_along_axis(accb, idx, axis=-1)
    kept = jnp.any(idx[..., None] == jnp.arange(block), axis=-2)   # [W, nb, block]
    res_new = jnp.where(kept, 0.0, accb).reshape(W, nb * block)[:, :n]
    return (values.reshape(W, nb * k), idx.astype(jnp.int32).reshape(W, nb * k),
            res_new)


def topk_decode(values, idx, n: int, *, k: int, block: int):
    """Inverse of :func:`topk_encode`: scatter the kept (value, index) pairs
    back into a dense zero buffer -> [W, n] float32."""
    W = values.shape[0]
    nb = values.shape[1] // k
    v = values.reshape(W, nb, k)
    i = idx.reshape(W, nb, k)
    onehot = (i[..., None] == jnp.arange(block)).astype(jnp.float32)
    dense = jnp.sum(onehot * v[..., None], axis=-2)           # [W, nb, block]
    return dense.reshape(W, nb * block)[:, :n]


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              logit_softcap: float = 0.0, q_offset: int = 0, kv_len=None):
    """Naive full-softmax attention oracle.

    q: [B, Sq, H, hd]; k, v: [B, Skv, Hkv, d*]. Materializes [B,H,Sq,Skv] —
    small test shapes only.
    """
    B, Sq, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    kr = jnp.repeat(k, G, axis=2)
    vr = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kr.astype(jnp.float32))
    s = s * hd ** -0.5
    if logit_softcap:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    q_pos = q_offset + jnp.arange(Sq)[:, None]
    kv_pos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kv_pos <= q_pos
    if window:
        mask &= (q_pos - kv_pos) < window
    if kv_len is not None:
        mask &= kv_pos < kv_len
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32))
    return o.astype(q.dtype)
