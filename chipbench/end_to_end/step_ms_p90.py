"""90th percentile of the time of a step, over every step of the
window: the host's clock between the results of consecutive readings
(``steps_per_reading`` steps each, so that a reading spans 250 ms or
more), per step.  In synchronous data parallelism the slow step is what
every rank waits for."""

import statistics


def read(ctx):
    readings = ctx["window"]["reading_seconds"]
    per_step = [1e3 * r / ctx["window"]["steps_per_reading"]
                for r in readings]
    if len(per_step) < 2:
        return per_step[0]
    return statistics.quantiles(per_step, n=10, method="inclusive")[8]
