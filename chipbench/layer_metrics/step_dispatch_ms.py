"""Median host time of one call ``step(state, batch)`` until it
returns (the enqueue, not the device's work), over the window."""

import statistics

COUNTERS = []


def read(ctx):
    spans = ctx["spans"].get("step_dispatch")
    return 1e3 * statistics.median(spans) if spans else None
