"""Operations a training step requires, per token.

Each model family counts its own, beside its plain reference:
``refs/<reference>.py`` has ``forward_flops(cfg, seq)``, the forward
operations per token by named term, from the family's published equations.
Every family keeps one rule: count the work the model requires, not the work
an implementation does. Forward operations per token are 2 x the matmul
weights a token passes (the LM head included, the embedding gather left out)
plus the sequence-mixing terms at the cell's sequence length, causal pairs
only; a training step is forward + backward, 3 x forward. Recomputation in
the backward pass is not counted. Elementwise work (norms, gates, activations,
the softmax) is not counted either. A kernel's roofline share
(``roofline.py``) takes its operations from the same terms.
"""
from __future__ import annotations

import harness


def forward_terms(cfg: dict, seq: int) -> dict:
    """Forward operations per token by term, from the family's reference."""
    return harness.reference_model(cfg).forward_flops(cfg, seq)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward operations per token of one training step."""
    return 3.0 * sum(forward_terms(cfg, seq).values())
