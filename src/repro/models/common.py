"""Shared model building blocks (functional, pytree params).

Every ``init_*`` returns ``(params, axes)`` — parallel dicts where each axes
leaf is a tuple of *logical* axis names per array dim (see
repro/launch/sharding.py). Keeping axes with the initializers means the
sharding rules never guess from parameter names.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def dense_init(key, shape: Tuple[int, ...], axes: Tuple[Optional[str], ...],
               dtype=jnp.float32, fan_in: Optional[int] = None, scale: float = 1.0):
    """He/Kaiming-style variance scaling (paper §4.1 uses Kaiming init)."""
    fi = fan_in if fan_in is not None else shape[0]
    std = scale * float(np.sqrt(2.0 / max(fi, 1)))
    return jax.random.normal(key, shape, dtype) * std, axes


def zeros_init(shape, axes, dtype=jnp.float32):
    return jnp.zeros(shape, dtype), axes


def ones_init(shape, axes, dtype=jnp.float32):
    return jnp.ones(shape, dtype), axes


def split_tree(pairs: dict) -> Tuple[dict, dict]:
    """{'name': (param, axes)} possibly nested -> (params, axes) trees."""
    params, axes = {}, {}
    for k, v in pairs.items():
        if isinstance(v, dict):
            params[k], axes[k] = split_tree(v)
        else:
            params[k], axes[k] = v
    return params, axes


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.float32):
    return ones_init((d,), ("act_embed",), dtype)


def rmsnorm(w, x, eps: float = 1e-5, plus_one: bool = False):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    scale = (1.0 + w.astype(jnp.float32)) if plus_one else w.astype(jnp.float32)
    return (y * scale).astype(x.dtype)


def init_layernorm(d: int, dtype=jnp.float32):
    return {"w": ones_init((d,), ("act_embed",), dtype),
            "b": zeros_init((d,), ("act_embed",), dtype)}


def layernorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["w"].astype(jnp.float32) + p["b"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., S, H, hd]; positions: [..., S] or [S]."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                            # [hd/2]
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., :, None, :]                               # broadcast over heads
    sin = sin[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    """Gemma2 logit soft-capping: cap * tanh(x / cap)."""
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def maybe_shard(x, *spec):
    """with_sharding_constraint against the ambient mesh, if any.

    Model code stays mesh-agnostic: axis names that don't exist in the
    current mesh (or no mesh at all — unit tests on CPU) degrade to
    unconstrained. Each entry may be a name or tuple of names.
    """
    axes = set(jax.sharding.get_abstract_mesh().axis_names)   # ambient mesh
    if not axes:
        return x

    def keep(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a in axes)
            return kept or None
        return e if e in axes else None

    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(*(keep(e) for e in spec)))


def activation_fn(name: str):
    return {
        "relu": jax.nn.relu,
        "gelu": jax.nn.gelu,
        "silu": jax.nn.silu,
        "swiglu": jax.nn.silu,   # gating handled by the MLP module
        "geglu": jax.nn.gelu,
    }[name]
