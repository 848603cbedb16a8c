"""idle_share.solve: share of the traced window in which no op ran on the
chip, in %, averaged over the chips."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    window = ctx.record["t_end"] - ctx.record["t_start"]
    return 100.0 * (1.0 - ctx.trace.busy_s() / window)
