"""Host time per step, over the window, that the compiled step spends
staging the ranks' host batches onto its mesh (0 where the batch was
placed once with ``place_batch``)."""

from chipbench import scope_join

COUNTERS = ["horovod_step_calls_total",
            "horovod_step_stage_batch_seconds_total"]


def read(ctx):
    return scope_join.per_step_ms(ctx, COUNTERS[1])
