"""Feed-forward blocks: SwiGLU/GeGLU (gated) and plain 2-layer MLPs."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.models.common import activation_fn, dense_init, split_tree


def init_ffn(key, d_model: int, d_ff: int, activation: str, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    if activation in ("swiglu", "geglu"):
        return split_tree({
            "w_gate": dense_init(ks[0], (d_model, d_ff), ("embed", "ffn"), dtype),
            "w_up": dense_init(ks[1], (d_model, d_ff), ("embed", "ffn"), dtype),
            "w_down": dense_init(ks[2], (d_ff, d_model), ("ffn", "embed"), dtype, fan_in=d_ff),
        })
    return split_tree({
        "w_up": dense_init(ks[0], (d_model, d_ff), ("embed", "ffn"), dtype),
        "w_down": dense_init(ks[1], (d_ff, d_model), ("ffn", "embed"), dtype, fan_in=d_ff),
    })


@jax.named_scope("ffn")
def ffn_forward(p, x, activation: str):
    act = activation_fn(activation)
    if "w_gate" in p:
        h = act(x @ p["w_gate"].astype(x.dtype)) * (x @ p["w_up"].astype(x.dtype))
    else:
        h = act(x @ p["w_up"].astype(x.dtype))
    return h @ p["w_down"].astype(x.dtype)


def init_ffn_cfg(key, cfg: ModelConfig, dtype=jnp.float32):
    return init_ffn(key, cfg.d_model, cfg.d_ff, cfg.activation, dtype)
