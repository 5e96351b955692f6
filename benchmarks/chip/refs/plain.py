"""Pieces the plain references share: seeded initialisation, RMSNorm and the
next-token cross-entropy, written out in plain ``jax.numpy``.

Nothing here imports the system under test. A reference computes in the dtype
of the parameters it is handed: float32 at ``highest`` matmul precision for the
comparison, bfloat16 for the control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


class Init:
    """Draws each leaf from its own key, ``fold_in(key, n)`` for the n-th leaf
    drawn, so the weights depend on the seed and the draw order alone."""

    def __init__(self, key):
        self.key = key
        self.n = 0

    def _next(self):
        self.n += 1
        return jax.random.fold_in(self.key, self.n)

    def normal(self, shape, fan_in: int, scale: float = 1.0):
        """Kaiming-style normal: std = scale * sqrt(2 / fan_in)."""
        std = scale * math.sqrt(2.0 / max(fan_in, 1))
        return jax.random.normal(self._next(), shape, jnp.float32) * std

    @staticmethod
    def const(shape, value: float):
        return jnp.full(shape, value, jnp.float32)


def stack_layers(layers):
    """A list of per-layer dicts -> one dict of arrays stacked on axis 0."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def rmsnorm(w, x, eps: float):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def cross_entropy(hidden, head, labels):
    """Mean over all positions of -log softmax(hidden @ head)[label]."""
    logits = hidden @ head
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)

