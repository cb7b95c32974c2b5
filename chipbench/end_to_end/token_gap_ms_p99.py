"""99th percentile, over every token the window handed out after a
request's first, of the gap to that request's previous token on the
host's clock: the longest stall a stream's reader sees but for one in a
hundred.  Admission runs inside the tick, so it is one decode tick plus
the prefill of the widest prompt bucket the window admitted."""

import statistics


def read(ctx):
    gaps = ctx["window"]["reading_seconds"]
    return 1e3 * statistics.quantiles(gaps, n=100, method="inclusive")[98]
