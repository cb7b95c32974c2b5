"""Share of the traced span in which a prefill or an ingest ran on the
device, and so no slot could decode: admission runs inside the tick."""

from chipbench import serve_trace

COUNTERS = []


def read(ctx):
    found = serve_trace.runs_in_span(ctx)
    if not found:
        return None
    runs, span = found
    return (serve_trace.seconds_of(runs["prefill"])
            + serve_trace.seconds_of(runs["ingest"])) / (span[1] - span[0])
