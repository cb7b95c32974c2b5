"""Device time of the forward pass per traced step, the mean over the
chips: the leaf operations under ``hvd_step/loss_and_grad`` that are
neither the transposed ``jvp`` nor a recomputation (``chipbench/scope_join.py``)."""

from chipbench import scope_join

COUNTERS = []


def read(ctx):
    return scope_join.read(ctx, "phase", "forward")
