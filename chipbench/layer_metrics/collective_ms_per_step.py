"""Device time of the collective operations (all-reduce, reduce-scatter,
all-gather, ...) per traced step, on the chip where it is longest."""

from chipbench import trace_reduce

COUNTERS = []


def read(ctx):
    if not ctx.get("trace"):
        return None
    found = trace_reduce.collective_seconds(ctx["trace"])
    total = max(t for t, _ in found.values())
    return 1e3 * total / ctx["trace_steps"] if total > 0 else None
