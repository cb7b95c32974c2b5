"""Median, over every token the window handed out after a request's
first, of the gap to that request's previous token on the host's clock:
the speed a stream's reader sees.  Under load it is one decode tick at
the widest block-table bucket among the active slots; the 90th
percentile (``step_ms_p90`` in a served cell) adds a prefill's stall."""

import statistics


def read(ctx):
    return 1e3 * statistics.median(ctx["window"]["reading_seconds"])
