"""tick_ms.serve: mean host-clock wall of ``DetectionService.step_tick``,
in ms, over the ticks of the run."""


def read(ctx):
    ticks = ctx.record.get("ticks")
    if not ticks:
        return None
    return 1e3 * sum(t1 - t0 for _, t0, t1 in ticks) / len(ticks)
