"""Device time, per traced step, of the forward that the backward pass
recomputes (``rematted_computation`` in an operation's path: the remat
of the decoder blocks and of the cross-entropy's chunks), the mean over
the chips (``chipbench/scope_join.py``)."""

from chipbench import scope_join

COUNTERS = []


def read(ctx):
    return scope_join.read(ctx, "phase", "remat")
