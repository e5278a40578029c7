"""window_compiles: executables that JAX built inside the traced window,
compiled or loaded from the persistent cache: the marks that
``repro.obs.metrics.watch_compiles`` leaves on the host timeline at each
increment of ``jax.backend_compiles``. None where the process keeps no such
counter."""
from chipbench.lib import scopes


def read(ctx):
    from repro.obs import metrics
    if scopes.COMPILES not in metrics.DEFAULT_REGISTRY.snapshot()["counters"]:
        return None
    return scopes.marks(ctx.trace, ctx.lo, ctx.hi, scopes.COMPILES)
