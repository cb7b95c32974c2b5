"""Device time, per traced step, of a looped model's recurrent stack
(the scope ``loop``: every pass's layers and final norm; forward,
backward and recomputation), the mean over the chips
(``chipbench/scope_time.py``)."""

from chipbench import scope_time

COUNTERS = []


def read(ctx):
    return scope_time.ms_per_step(ctx, scope_time.component("loop"))
