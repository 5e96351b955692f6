"""Jit'd dispatch wrappers for the Pallas kernels.

On the TPU every entry point runs its compiled Pallas kernel. Off the TPU
the default (``use_kernel=None``) runs the jnp oracle from
:mod:`repro.kernels.ref`: that path is for tests and CPU rehearsals, never a
stand-in for the chip. ``use_kernel=True`` off the TPU runs the kernel body
in interpret mode — bit-faithful to the kernel, slow — which is how the tests
check kernel-vs-oracle parity.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.common.flat import FlatSpec
from repro.kernels import codec as _codec
from repro.kernels import fused_update as _fu
from repro.kernels import flash_attention as _fa
from repro.kernels import ref
from repro.kernels import robust as _rb

PyTree = Any


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def fused_elastic_nag_update(theta, peer, v, g, coef_gate, *, eta: float, mu: float,
                             use_kernel: Optional[bool] = None, interpret: Optional[bool] = None):
    """Tree-ready fused update; see kernels/ref.py for the math."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if not use_kernel:
        return ref.fused_elastic_nag_update(
            theta, peer, v, g,
            coef_gate=coef_gate, eta=eta, mu=mu)
    return _fu.fused_elastic_nag_update(
        theta, peer, v, g, coef_gate, eta=eta, mu=mu,
        interpret=(not on_tpu()) if interpret is None else interpret)


# ---------------------------------------------------------------------------
# Flat-plane entry points (repro.common.flat buffers / whole pytrees)
# ---------------------------------------------------------------------------

def fused_flat_elastic_nag_update(theta, peer, v, g, coef, eta, mu, *,
                                  use_kernel: Optional[bool] = None,
                                  interpret: Optional[bool] = None):
    """[W, N] flat-buffer fused update; per-replica coef, traced eta/mu."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if not use_kernel:
        return ref.fused_flat_elastic_nag_update(theta, peer, v, g, coef, eta, mu)
    return _fu.fused_flat_elastic_nag_update(
        theta, peer, v, g, coef, eta, mu,
        interpret=(not on_tpu()) if interpret is None else interpret)


def fused_flat_nag_update(theta, v, g, eta, mu, *,
                          use_kernel: Optional[bool] = None,
                          interpret: Optional[bool] = None):
    """[W, N] flat-buffer pure-NAG update (no peer stream)."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if not use_kernel:
        return ref.fused_flat_nag_update(theta, v, g, eta, mu)
    return _fu.fused_flat_nag_update(
        theta, v, g, eta, mu,
        interpret=(not on_tpu()) if interpret is None else interpret)


def robust_flat_apply(theta, delta, scale, thr, *,
                      use_kernel: Optional[bool] = None,
                      interpret: Optional[bool] = None):
    """[W, N] robust displacement apply: theta + scale * trim(delta, thr) —
    the robust-gossip protocols' one pass over the flat plane."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if not use_kernel:
        return ref.robust_flat_apply(theta, delta, scale, thr)
    return _rb.robust_flat_apply(
        theta, delta, scale, thr,
        interpret=(not on_tpu()) if interpret is None else interpret)


def robust_bufs_apply(theta_bufs, delta_bufs, scale, thr, *,
                      use_kernel: Optional[bool] = None,
                      interpret: Optional[bool] = None):
    """Per-dtype-bucket dispatch of :func:`robust_flat_apply` over flat-buffer
    dicts (the robust protocols' comm hot path)."""
    return {k: robust_flat_apply(theta_bufs[k], delta_bufs[k], scale, thr,
                                 use_kernel=use_kernel, interpret=interpret)
            for k in theta_bufs}


def fused_bufs_elastic_nag(theta_bufs, peer_bufs, v_bufs, g_bufs, coef, eta, mu,
                           *, use_kernel: Optional[bool] = None,
                           interpret: Optional[bool] = None):
    """Per-dtype-bucket dispatch of the fused update over flat-buffer dicts —
    the flat-resident engines' communicating hot path (the sim engine calls
    it on the resident FlatState buffers; the dist engine through the
    shard-mapped ``gossip_dist`` fused mode). The kernel aliases theta/v into
    its outputs, so donated resident buffers update in place. Returns
    (theta'_bufs, v'_bufs)."""
    out_t, out_v = {}, {}
    for k in theta_bufs:
        out_t[k], out_v[k] = fused_flat_elastic_nag_update(
            theta_bufs[k], peer_bufs[k], v_bufs[k], g_bufs[k], coef, eta, mu,
            use_kernel=use_kernel, interpret=interpret)
    return out_t, out_v


def fused_tree_elastic_nag(theta: PyTree, peer: PyTree, v: PyTree, g: PyTree,
                           coef, *, eta, mu, spec: Optional[FlatSpec] = None,
                           use_kernel: Optional[bool] = None,
                           interpret: Optional[bool] = None):
    """Tree-level fused update in ONE pass per dtype bucket over the flat
    plane (Alg. 5 lines 3/7/9, simultaneous). Since the flat-resident
    FlatState redesign the engines call :func:`fused_bufs_elastic_nag` on
    their resident buffers directly; this tree wrapper remains the
    oracle/benchmark surface (and measures exactly the per-call
    flatten/unflatten cost the resident layout deleted — see
    benchmarks/fused_step.py ``update_phase``).

    All four trees share ``theta``'s structure, stacked ``[W, ...]``; ``coef``
    is the per-replica moving rate * gate (scalar or [W]); ``spec`` is the
    cached :class:`FlatSpec` (built from ``theta`` when omitted). Returns
    (theta', v') as trees with theta's / v's dtypes.

    For UNSHARDED stacked trees only (the sim engine / tests): a pallas_call
    has no GSPMD sharding rule, so on sharded trees XLA would all-gather the
    plane — the dist engine instead reaches the flat kernels through the
    shard-mapped ``gossip_dist.make_gossip_step(mode="fused")`` /
    ``DistTrainer.fused_nag`` programs, which hand the kernel local shards.
    """
    if spec is None:
        spec = FlatSpec.build(theta, leading=1)
    out_t, out_v = fused_bufs_elastic_nag(
        spec.flatten(theta), spec.flatten(peer), spec.flatten(v), spec.flatten(g),
        coef, eta, mu, use_kernel=use_kernel, interpret=interpret)
    return spec.unflatten(out_t, like=theta), spec.unflatten(out_v, like=v)


def fused_bufs_nag(theta_bufs, v_bufs, g_bufs, eta, mu, *,
                   use_kernel: Optional[bool] = None,
                   interpret: Optional[bool] = None):
    """Per-dtype-bucket pure-NAG update over flat-buffer dicts — the
    flat-resident engines' non-firing hot path (no flatten, and the kernel
    aliases theta/v into its outputs for a true in-place update)."""
    out_t, out_v = {}, {}
    for k in theta_bufs:
        out_t[k], out_v[k] = fused_flat_nag_update(
            theta_bufs[k], v_bufs[k], g_bufs[k], eta, mu,
            use_kernel=use_kernel, interpret=interpret)
    return out_t, out_v


def fused_tree_nag(theta: PyTree, v: PyTree, g: PyTree, *, eta, mu,
                   spec: Optional[FlatSpec] = None,
                   use_kernel: Optional[bool] = None,
                   interpret: Optional[bool] = None):
    """Tree-level pure-NAG flat update (the non-firing step of pairwise
    protocols): velocity + parameter update in one pass, 5 streams."""
    if spec is None:
        spec = FlatSpec.build(theta, leading=1)
    out_t, out_v = fused_bufs_nag(spec.flatten(theta), spec.flatten(v),
                                  spec.flatten(g), eta, mu,
                                  use_kernel=use_kernel, interpret=interpret)
    return spec.unflatten(out_t, like=theta), spec.unflatten(out_v, like=v)


# ---------------------------------------------------------------------------
# Gossip-compression codec entry points (repro.comm; [W, N] flat buckets)
# ---------------------------------------------------------------------------

def _pick(use_kernel: Optional[bool], interpret: Optional[bool]):
    if use_kernel is None:
        use_kernel = on_tpu()
    return use_kernel, (not on_tpu()) if interpret is None else interpret


def q8_encode(buf, seeds, *, block: int, use_kernel: Optional[bool] = None,
              interpret: Optional[bool] = None):
    """Stochastic-rounding int8 quantization -> (values, per-block scales)."""
    use_kernel, interpret = _pick(use_kernel, interpret)
    if not use_kernel:
        return ref.q8_encode(buf, seeds, block=block)
    return _codec.q8_encode(buf, seeds, block=block, interpret=interpret)


def q8_decode(values, scales, n: int, *, block: int,
              use_kernel: Optional[bool] = None, interpret: Optional[bool] = None):
    use_kernel, interpret = _pick(use_kernel, interpret)
    if not use_kernel:
        return ref.q8_decode(values, scales, n, block=block)
    return _codec.q8_decode(values, scales, n=n, block=block, interpret=interpret)


def topk_encode(buf, residual, *, k: int, block: int,
                use_kernel: Optional[bool] = None, interpret: Optional[bool] = None):
    """Per-block magnitude top-k with error feedback ->
    (values, indices, residual')."""
    use_kernel, interpret = _pick(use_kernel, interpret)
    if residual is None:
        residual = jnp.zeros(buf.shape, jnp.float32)
    if not use_kernel:
        return ref.topk_encode(buf, residual, k=k, block=block)
    return _codec.topk_encode(buf, residual, k=k, block=block, interpret=interpret)


def topk_decode(values, idx, n: int, *, k: int, block: int,
                use_kernel: Optional[bool] = None, interpret: Optional[bool] = None):
    use_kernel, interpret = _pick(use_kernel, interpret)
    if not use_kernel:
        return ref.topk_decode(values, idx, n, k=k, block=block)
    return _codec.topk_decode(values, idx, n=n, k=k, block=block,
                              interpret=interpret)


def flash_attention(q, k, v, kv_len=None, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_offset: int = 0,
                    use_kernel: Optional[bool] = None, interpret: Optional[bool] = None,
                    block_q: int = 128, block_k: int = 512):
    """q: [B, H, Sq, hd]; k, v: [B, Hkv, Skv, hd] (BHSD layout)."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if not use_kernel:
        # ref takes BSHD layout
        o = ref.attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
                          causal=causal, window=window, logit_softcap=softcap,
                          q_offset=q_offset, kv_len=kv_len)
        return jnp.swapaxes(o, 1, 2)
    return _fa.flash_attention(
        q, k, v, kv_len, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=(not on_tpu()) if interpret is None else interpret)
