"""90th percentile of the host time of one ``submit`` call over the
window: the call takes the batcher's lock, which the tick thread holds
for a whole tick (admissions and their prefills included) and takes
again at once."""

import statistics

COUNTERS = []


def read(ctx):
    waits = ctx["spans"].get("step_dispatch") if "serve" in ctx else None
    if not waits or len(waits) < 2:
        return None
    return 1e3 * statistics.quantiles(waits, n=10, method="inclusive")[8]
