"""Assignments to held experts that the window's steps did not compute
(``horovod_moe_dropped_assignments_total``): 0, or the routed layer is
not dropless."""

from chipbench import scope_join

COUNTERS = ["horovod_moe_assignments_total",
            "horovod_moe_dropped_assignments_total"]


def read(ctx):
    if scope_join.counter_delta(ctx, COUNTERS[0]) <= 0:
        return None                 # a commit without the counters
    return scope_join.counter_delta(ctx, COUNTERS[1])
