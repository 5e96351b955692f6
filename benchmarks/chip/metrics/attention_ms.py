"""attention_ms: device ms per step under the ``attention`` scope
(``models/attention.py``: the q/k/v/o projections, RoPE and the chunked
online-softmax attention), forward and backward, from the traced window
(``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "attention")
