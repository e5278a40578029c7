"""mlp_frac: share of the traced window in which a leaf op of the ``mlp`` scope
ran on a device, averaged over the devices. The scope is
``repro.models.mlp.mlp``, with its recompute and backward. None on a program
without the scopes."""
from chipbench.lib import scopes


def read(ctx):
    return scopes.scope_frac(ctx, "mlp")
