"""Device time of one decode tick: the decode programs' summed time in
the traced span over their runs.  A tick decodes every slot at the
widest block-table bucket among the active ones."""

from chipbench import serve_trace

COUNTERS = []


def read(ctx):
    found = serve_trace.runs_in_span(ctx)
    if not found or not found[0]["decode"]:
        return None
    runs = found[0]["decode"]
    return 1e3 * serve_trace.seconds_of(runs) / len(runs)
