"""Device time under ``kda`` per traced step (a delta-rule layer's whole
mixer: the projections of its input, the three convolutions, the rule,
the gated norm, the output projection; forward, backward and
recomputation), the mean over the chips (``chipbench/scope_time.py``)."""

from chipbench import scope_time

COUNTERS = []


def read(ctx):
    return scope_time.ms_per_step(ctx, scope_time.component("kda"))
