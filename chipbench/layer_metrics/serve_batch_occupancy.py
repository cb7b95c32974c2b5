"""Tokens a decode tick emits over the slots it computes: the tokens
decode chose in the traced span, over its ticks, over ``max_slots``
(every tick computes all of them, masked or not)."""

from chipbench import serve_trace

COUNTERS = []


def read(ctx):
    found = serve_trace.runs_in_span(ctx)
    if not found or not found[0]["decode"]:
        return None
    runs, span = found
    tokens = serve_trace.stamps_in(
        ctx, serve_trace.host_span(span), first=False)
    return len(tokens) / len(runs["decode"]) / ctx["serve"]["max_slots"]
