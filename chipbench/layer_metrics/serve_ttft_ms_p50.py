"""Median, over the requests that arrived in the window, of scheduled
arrival to first token: steadier than the tail that is judged."""

import statistics

COUNTERS = []


def read(ctx):
    if "serve" not in ctx:
        return None
    start, end = ctx["serve"]["spans"]["window"]
    waits = [1e3 * (r["stamps"][0] - r["due"])
             for r in ctx["serve"]["requests"]
             if r["stamps"] and start <= r["due"] < end]
    return statistics.median(waits) if waits else None
