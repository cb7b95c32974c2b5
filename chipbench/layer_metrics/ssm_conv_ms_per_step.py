"""Device time under ``mamba`` + ``conv`` per traced step (the causal
depthwise convolution with its bias and silu; forward, backward and
recomputation), the mean over the chips (``chipbench/scope_time.py``)."""

from chipbench import scope_time

COUNTERS = []


def read(ctx):
    return scope_time.ms_per_step(ctx, scope_time.under("mamba", "conv"))
