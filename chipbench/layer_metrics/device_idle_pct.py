"""Share of the traced window in which no operation ran, on the chip
where that share is largest."""

from chipbench import trace_reduce

COUNTERS = []


def read(ctx):
    if not ctx.get("trace"):
        return None
    return 100.0 * max(trace_reduce.idle_share(ctx["trace"]).values())
