"""The part of the collective operations' device time during which no
other operation runs on that chip, per traced step, on the chip where
it is longest: what overlap with the backward pass could still hide."""

from chipbench import trace_reduce

COUNTERS = []


def read(ctx):
    if not ctx.get("trace"):
        return None
    found = trace_reduce.collective_seconds(ctx["trace"])
    if max(t for t, _ in found.values()) <= 0:
        return None
    return 1e3 * max(e for _, e in found.values()) / ctx["trace_steps"]
