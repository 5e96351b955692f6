"""ffn_ms: device ms per step under the ``ffn`` scope (``models/mlp.py``: the
SwiGLU FFN's three matmuls and its gate), forward and backward, from the
traced window (``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "ffn")
