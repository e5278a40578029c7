"""dispatch_wait_ms: device idle time (no op on the device) while the host was
inside ``trainer.dispatch``, where the step was called and dispatched,
averaged over the devices, per ``trainer.step`` span of the window (ms).
None where the window has no ``trainer.step`` span."""
from chipbench.lib import scopes


def read(ctx):
    return scopes.wait_ms(ctx.trace, ctx.lo, ctx.hi, "trainer.dispatch")
