"""attention_frac: share of the traced window in which a leaf op of the
``attention`` scope ran on a device, averaged over the devices. The scope is
``repro.models.attention.self_attention``: projections and scores, with
their recompute and backward. None on a program without the scopes."""
from chipbench.lib import scopes


def read(ctx):
    return scopes.scope_frac(ctx, "attention")
