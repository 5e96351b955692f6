"""embed_head_ms: device ms per step of ``models/transformer.py``'s own work:
the ``embed`` scope (token embedding), the ``layer_scan`` scope (the scan over
layers: per-layer weight slices, norms, residual adds; attention and the FFN
inside it carry their own scopes) and the ``head_loss`` scope (the tied head,
the logits and the chunked cross-entropy), forward and backward, from the
traced window (``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "embed", "layer_scan", "head_loss")
