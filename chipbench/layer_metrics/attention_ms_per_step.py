"""Device time of the decoder blocks' attention per traced step
(``layers/attn``: the projections, rope and the flash kernels; forward,
backward and recomputation), the mean over the chips
(``chipbench/scope_join.py``)."""

from chipbench import scope_join

COUNTERS = []


def read(ctx):
    return scope_join.read(ctx, "part", "attention")
