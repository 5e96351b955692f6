"""grad_mean_ms: device ms per step under the ``grad_mean`` scope (the
protocol's gradient transform, ``api/protocols.py``: the mean over the
replicas under all-reduce), from the traced window (``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "grad_mean")
