"""Device time under ``kda`` + ``delta`` per traced step (the delta
rule: everything between the convolutions and the gated norm, the
norms of q and k, the decay and the chunked recurrence with its pass
over the chunks; forward, backward and recomputation), the mean over
the chips (``chipbench/scope_time.py``)."""

from chipbench import scope_time

COUNTERS = []


def read(ctx):
    return scope_time.ms_per_step(ctx, scope_time.under("kda", "delta"))
