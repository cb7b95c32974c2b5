"""Time a rank thread spends at the compiled step's rendezvous between its
own arrival and the last rank's, per step and rank, over the window:
the skew between the threads (the leader's launch they then wait for is
``step_stage_batch_ms`` + ``step_program_call_ms``)."""

from chipbench import scope_join

COUNTERS = ["horovod_step_calls_total",
            "horovod_step_rendezvous_wait_seconds_total"]


def read(ctx):
    return scope_join.per_step_ms(ctx, COUNTERS[1], per_rank=True)
