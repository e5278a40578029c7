"""optimizer_frac: share of the traced window in which a leaf op of the
``optimizer`` scope ran on a device, averaged over the devices. The scope is
the AdamW update of ``repro.train.optimizer``, global norm and clip
included. None on a program without the scopes."""
from chipbench.lib import scopes


def read(ctx):
    return scopes.scope_frac(ctx, "optimizer")
