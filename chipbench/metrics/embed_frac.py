"""embed_frac: share of the traced window in which a leaf op of the ``embed``
scope ran on a device, averaged over the devices. The scope is
``repro.models.transformer.embed_tokens``: the token lookup and, in the
backward pass, the scatter of its gradient. None on a program without the
scopes."""
from chipbench.lib import scopes


def read(ctx):
    return scopes.scope_frac(ctx, "embed")
