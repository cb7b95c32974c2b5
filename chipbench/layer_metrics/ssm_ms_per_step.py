"""Device time under ``mamba`` per traced step (a state-space layer's
whole mixer: in-projection, convolution, scan, gated norm,
out-projection; forward, backward and recomputation), the mean over the
chips (``chipbench/scope_time.py``)."""

from chipbench import scope_time

COUNTERS = []


def read(ctx):
    return scope_time.ms_per_step(ctx, scope_time.component("mamba"))
