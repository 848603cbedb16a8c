"""unscoped_ms.solve: device ms per outer step of the ops under no
``repro.`` scope (copies the compiler inserts, such as the while loop's
carry, and work outside the loop), averaged over the chips."""
from bench.trace import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, scopes.UNSCOPED)
