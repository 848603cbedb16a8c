"""collective_exposed_ms.solve: collective time with no other op running
on the chip, per outer iteration, in ms, averaged over the chips."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    solves = ctx.record.get("solves") or []
    iters = sum(s["outer_iters"] for s in solves)
    devs = ctx.trace.devices
    exposed = [ctx.trace.exposed_collective_ns(d) for d in devs]
    if not iters or not any(exposed):
        return None
    return sum(exposed) / len(devs) / iters / 1e6
