"""sweep_ms.solve: device ms per outer step under the program's
``repro.sweep`` scope (the sweep kernels), averaged over the chips."""
from bench.trace import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "sweep")
