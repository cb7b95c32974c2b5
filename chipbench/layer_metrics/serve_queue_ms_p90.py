"""90th percentile, over the requests that arrived in the window, of
what a request waited before its own prefill began: scheduled arrival to
first token, less the device time of a prefill and ingest of its prompt
bucket (the traced span's mean for that bucket; where the span ran none
of it, of all buckets scaled by rows).  Holds the wait for the server's
lock, for a slot and its blocks, and for the admissions ahead."""

import statistics

from chipbench import serve_trace

COUNTERS = []


def read(ctx):
    found = serve_trace.runs_in_span(ctx)
    if not found or not found[0]["prefill"]:
        return None
    runs = found[0]
    per_row = (serve_trace.seconds_of(runs["prefill"])
               + serve_trace.seconds_of(runs["ingest"]))
    requests = serve_trace.prefilled(ctx, runs, found[1])
    if not requests:
        return None

    def bucket(r):
        return serve_trace.prompt_bucket(ctx, r)

    rows = sum(map(bucket, requests))
    per_row /= rows
    start, end = ctx["serve"]["spans"]["window"]
    waits = [1e3 * (r["stamps"][0] - r["due"] - per_row * bucket(r))
             for r in ctx["serve"]["requests"]
             if r["stamps"] and start <= r["due"] < end]
    if len(waits) < 2:
        return None
    return statistics.quantiles(waits, n=10, method="inclusive")[8]
