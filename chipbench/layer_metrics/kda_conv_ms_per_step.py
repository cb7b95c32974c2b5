"""Device time under ``kda`` + ``conv`` per traced step (the three
causal depthwise convolutions with their silu; forward, backward and
recomputation), the mean over the chips (``chipbench/scope_time.py``)."""

from chipbench import scope_time

COUNTERS = []


def read(ctx):
    return scope_time.ms_per_step(ctx, scope_time.under("kda", "conv"))
