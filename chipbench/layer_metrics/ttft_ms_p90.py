"""90th percentile, over the requests that ARRIVED in the window, of
scheduled arrival to first token on the host's clock: a late generator
cannot flatter it, and the wait for the server's lock is in it.  A
request that got no token counts with the wait until the drain's end
(and fails the run).  Recorded, not judged: over the 40 arrivals of a
20 s window it spreads by 17 to 33% from seed to seed, and two runs of
ONE seed still differ by 2 to 15% (an arrival races the tick in flight
and the batcher's lock), so no order of the traffic admits it
(PERF.md, PR 42)."""

import statistics

COUNTERS = []


def read(ctx):
    if "serve" not in ctx:
        return None
    serve = ctx["serve"]
    start, end = serve["spans"]["window"]
    waits = [1e3 * ((r["stamps"][0] if r["stamps"] else serve["ended"])
                    - r["due"])
             for r in serve["requests"] if start <= r["due"] < end]
    if len(waits) < 2:
        return waits[0]
    return statistics.quantiles(waits, n=10, method="inclusive")[8]
