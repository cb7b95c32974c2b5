"""Device time per traced step, the mean over the chips, of the compute
fusions that carry a step of an asynchronous collective (the report's
``collectives`` of mode ``carried``: weight-gradient products and the
optimizer's updates with an all-reduce aboard).  Their communication
and their compute share one event: beside the same products' time on
one chip this bounds what the communication costs them
(``chipbench/report_time.py``)."""

import statistics

from chipbench import report_time

COUNTERS = []


def read(ctx):
    return report_time.read_collectives(
        ctx, report_time.mode_ms, mode="carried", over=statistics.fmean)
