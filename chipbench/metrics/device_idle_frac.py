"""device_idle_frac: 1 - (union of device-op intervals) / traced window,
averaged over the cell's devices."""
from chipbench.lib import trace


def read(ctx):
    return trace.idle_frac(ctx.trace, ctx.lo, ctx.hi)
