"""update_ms: device ms per step under the ``update`` scope (the optimizer
update of ``core/gossip_sim.py``, per-bucket NAG or the fused kernel, and its
counterpart in the dist engine), from the traced window (``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "update")
