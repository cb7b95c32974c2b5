"""Device time of the backward pass per traced step, the mean over the
chips, without the forward it recomputes: the operations of the
transposed ``jvp`` under ``hvd_step/loss_and_grad`` outside any
``rematted_computation`` (``chipbench/scope_join.py``)."""

from chipbench import scope_join

COUNTERS = []


def read(ctx):
    return scope_join.read(ctx, "phase", "backward")
