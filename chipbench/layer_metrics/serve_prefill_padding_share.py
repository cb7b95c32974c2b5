"""Share of the prefill programs' rows that are padding: over the
prompts prefilled in the window, 1 - their tokens over their prompt
buckets' rows."""

from chipbench import serve_trace

COUNTERS = []


def read(ctx):
    if "serve" not in ctx:
        return None
    start, end = ctx["serve"]["spans"]["window"]
    tokens = rows = 0
    for r in ctx["serve"]["requests"]:
        if r["stamps"] and start <= r["stamps"][0] < end:
            tokens += len(r["prompt"])
            rows += serve_trace.prompt_bucket(ctx, r)
    return 1.0 - tokens / rows if rows else None
