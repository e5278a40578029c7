"""step_gap_ms: mean device idle between the end of one execution of the
step program and the start of the next: the host's turn per step."""
from chipbench.lib import trace


def read(ctx):
    gaps = trace.step_gaps_ns(ctx.trace, ctx.lo, ctx.hi)
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
