"""detect_ms.solve: device ms per outer step under the program's
``repro.detect`` scope (the monitor, NFAIS2's verification included),
averaged over the chips."""
from bench.trace import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "detect")
