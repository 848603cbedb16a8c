"""outer_iters.solve: mean outer iterations per solve (the runtime's
exact ``outer_iters`` count)."""


def read(ctx):
    solves = ctx.record.get("solves")
    if not solves:
        return None
    return sum(s["outer_iters"] for s in solves) / len(solves)
