"""reduce_ms.solve: device ms per outer step under the program's
``repro.reduce`` scope (the blocking mode's residual-only pass, the
contribution ring, the collective, the residual record), averaged over the
chips."""
from bench.trace import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "reduce")
