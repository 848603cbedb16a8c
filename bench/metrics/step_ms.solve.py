"""step_ms.solve: host-clock solve time per outer iteration, in ms."""


def read(ctx):
    solves = ctx.record.get("solves")
    if not solves:
        return None
    iters = sum(s["outer_iters"] for s in solves)
    return 1e3 * sum(s["wall_s"] for s in solves) / iters if iters else None
