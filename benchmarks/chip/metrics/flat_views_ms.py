"""flat_views_ms: device ms per step under the ``flat_views`` scope
(``common/flat.py`` ``FlatSpec.views``: the slices that present the flat
parameter plane as the model's tree, and in the backward pass the scatter of
every leaf's gradient into a zeroed plane), from the traced window
(``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "flat_views")
