"""The load the held experts really saw: assignments that fell on held
experts over tokens x expert layers, in the window (1.0 under a balanced
router with the deployment's shares; ``mfu_pct``'s FLOP count assumes
1.0).  Both are the program's own counts, the first summed on the
device."""

from chipbench import scope_join

COUNTERS = ["horovod_moe_assignments_total",
            "horovod_moe_held_assignments_total"]


def read(ctx):
    assignments = scope_join.counter_delta(ctx, COUNTERS[0])
    if assignments <= 0:            # a commit without the counters
        return None
    return scope_join.counter_delta(ctx, COUNTERS[1]) \
        / (assignments / ctx["config"]["num_experts_per_tok"])
