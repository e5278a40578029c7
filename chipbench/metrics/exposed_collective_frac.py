"""exposed_collective_frac: share of the traced window in which a
collective (all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all, by HLO op name) runs on a device and no other op does, averaged
over the devices."""
from chipbench.lib import trace


def read(ctx):
    return trace.exposed_collective_frac(ctx.trace, ctx.lo, ctx.hi)
