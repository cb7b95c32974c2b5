"""Device time under ``moe`` + ``route`` per traced step (the router's
float32 product, softmax or sigmoid, top-k, the tokens by expert;
forward, backward and recomputation), the mean over the chips
(``chipbench/scope_time.py``).  In a model whose router reads the
layer's input this is what runs AHEAD of attention; the scope keeps its
path, so ``moe_ms_per_step`` and ``moe_route_dispatch_ms_per_step`` hold
it as they do for a router after attention."""

from chipbench import scope_time

COUNTERS = []


def read(ctx):
    return scope_time.ms_per_step(ctx, scope_time.under("moe", "route"))
