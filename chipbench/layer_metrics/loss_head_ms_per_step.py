"""Device time, per traced step, of the embedding lookup (``embed``) and
of the final projection with the fused chunked cross-entropy
(``lm_head_ce``), forward and backward, the mean over the chips
(``chipbench/scope_join.py``).  The head forms its gradient in the
forward pass since PR 33 (three vocabulary-sized products a step) and
is not recomputed."""

from chipbench import scope_join

COUNTERS = []


def read(ctx):
    return scope_join.read(ctx, "part", "loss_head")
