"""Of ``moe_ms_per_step``, the time under ``route`` (router product,
sigmoid, top-k, weights), ``dispatch`` (sort, gather into groups) and
``combine`` (weight, scatter back by token): what dropless routing costs
beside the products (``chipbench/scope_time.py``)."""

from chipbench import scope_time

COUNTERS = []


def read(ctx):
    return scope_time.ms_per_step(
        ctx, scope_time.component("moe") + ".*"
        + scope_time.component("route", "dispatch", "combine"))
