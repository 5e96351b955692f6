"""unscoped_share: the share of the device's busy time in the traced window
that falls under no scope of the program (``scopes.py``), in %. What stays
here is what no per-layer metric watches: the smaller it is, the less a
layer's change can hide."""
import scopes


def read(ctx):
    lay = scopes.layers(ctx)
    if lay is None or lay.busy_ns <= 0:
        return None
    return 100.0 * lay.ns.get("", 0.0) / lay.busy_ns
