"""head_frac: share of the traced window in which a leaf op of the ``head``
scope ran on a device, averaged over the devices. The scope is
``repro.models.transformer.lm_head`` and
``repro.models.common.softmax_cross_entropy``: final norm, logits and loss,
forward and backward. None on a program without the scopes."""
from chipbench.lib import scopes


def read(ctx):
    return scopes.scope_frac(ctx, "head")
