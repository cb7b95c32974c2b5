"""Device time of one admission: the prefill and ingest programs'
summed time in the traced span over the prefills run.  Nothing decodes
meanwhile."""

from chipbench import serve_trace

COUNTERS = []


def read(ctx):
    found = serve_trace.runs_in_span(ctx)
    if not found or not found[0]["prefill"]:
        return None
    runs = found[0]
    return 1e3 * (serve_trace.seconds_of(runs["prefill"])
                  + serve_trace.seconds_of(runs["ingest"])) \
        / len(runs["prefill"])
