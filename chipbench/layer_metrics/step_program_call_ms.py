"""Host time per step, over the window, inside the call of the step's
jitted program (the enqueue): the part of ``step_dispatch_ms`` that is
jax's."""

from chipbench import scope_join

COUNTERS = ["horovod_step_calls_total",
            "horovod_step_program_call_seconds_total"]


def read(ctx):
    return scope_join.per_step_ms(ctx, COUNTERS[1])
