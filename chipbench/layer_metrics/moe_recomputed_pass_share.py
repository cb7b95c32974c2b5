"""Of the passes the held experts' assignments took through their buffer
in the window (``horovod_moe_passes_total``: one a routed layer a step
under a router near balance), the share beyond a layer's first
(``horovod_moe_recomputed_passes_total``), whose forward products the
backward pass runs again: 0.0, or some layer's held load passed 5/4 of
the balance (``parallel/moe.held_buffer_rows``) and its steps are slow.
Both are the program's own counts, summed on the device."""

from chipbench import scope_join

COUNTERS = ["horovod_moe_passes_total",
            "horovod_moe_recomputed_passes_total"]


def read(ctx):
    passes = scope_join.counter_delta(ctx, COUNTERS[0])
    if passes <= 0:                 # a commit without the counters
        return None
    return scope_join.counter_delta(ctx, COUNTERS[1]) / passes
