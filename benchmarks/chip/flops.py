"""Operations a training step requires, per token, from the published
equations of each model family.

Forward operations per token are 2 x the matmul weights a token passes
(the LM head included, the embedding gather left out) plus the sequence-mixing
terms at the cell's sequence length; a training step is forward + backward,
3 x forward. Recomputation in the backward pass is not counted. Elementwise
work (norms, gates, activations, the softmax) is not counted either.
"""
from __future__ import annotations


def _dense_forward(cfg: dict, seq: int) -> float:
    """Dense decoder: GQA attention (q, k, v, o) and a SwiGLU FFN per layer,
    causal attention scores and values over the mean causal context."""
    d, V, F = cfg["d_model"], cfg["vocab_size"], cfg["d_ff"]
    H, Hkv = cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg.get("head_dim") or d // H
    ctx = (seq + 1) / 2
    weights = d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * F
    attention = 2.0 * 2 * H * hd * ctx       # q.k and p.v
    return cfg["num_layers"] * (2.0 * weights + attention) + 2.0 * d * V


FORWARD = {"dense": _dense_forward}


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward operations per token of one training step."""
    return 3.0 * FORWARD[cfg["reference"]](cfg, seq)

