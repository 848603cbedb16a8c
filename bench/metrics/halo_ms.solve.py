"""halo_ms.solve: device ms per outer step under the program's
``repro.halo`` scope (the exchange, the ghost ring, the face planes given
to each sweep), averaged over the chips."""
from bench.trace import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "halo")
