"""Device time of the decoder blocks' MLP per traced step (``layers/mlp``:
forward, backward and its recomputation), the mean over the chips
(``chipbench/scope_join.py``)."""

from chipbench import scope_join

COUNTERS = []


def read(ctx):
    return scope_join.read(ctx, "part", "mlp")
