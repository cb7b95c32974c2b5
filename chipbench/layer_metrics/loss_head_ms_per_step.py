"""Device time, per traced step, of the embedding lookup (``embed``) and
of the final projection with the fused chunked cross-entropy
(``lm_head_ce``), forward, backward and recomputation, the mean over the
chips (``chipbench/scope_join.py``)."""

from chipbench import scope_join

COUNTERS = []


def read(ctx):
    return scope_join.read(ctx, "part", "loss_head")
