"""Device time of the optimizer's update per traced step, the mean over
the chips: the operations under ``hvd_step/optimizer``
(``chipbench/scope_join.py``)."""

from chipbench import scope_join

COUNTERS = []


def read(ctx):
    return scope_join.read(ctx, "phase", "optimizer")
