"""Device time of the expert feed-forward per traced step (the scope
``moe``: router, dispatch, grouped products, shared expert, combine;
forward, backward and recomputation), the mean over the chips
(``chipbench/scope_time.py``)."""

from chipbench import scope_time

COUNTERS = []


def read(ctx):
    return scope_time.ms_per_step(ctx, scope_time.component("moe"))
