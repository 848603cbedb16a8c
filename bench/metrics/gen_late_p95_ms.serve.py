"""gen_late_p95_ms.serve: 95th percentile of how late the load generator
submitted each tenant (submit time minus due time), in ms."""

from bench.harness import nearest_rank


def read(ctx):
    late = ctx.record.get("late_s")
    if not late:
        return None
    return 1e3 * nearest_rank(late, 95)
