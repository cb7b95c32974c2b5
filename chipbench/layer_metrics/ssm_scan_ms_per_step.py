"""Device time under ``mamba`` + ``ssd`` per traced step (the selective
scan: everything between the convolution and the gated norm; forward,
backward and recomputation), the mean over the chips
(``chipbench/scope_time.py``)."""

from chipbench import scope_time

COUNTERS = []


def read(ctx):
    return scope_time.ms_per_step(ctx, scope_time.under("mamba", "ssd"))
