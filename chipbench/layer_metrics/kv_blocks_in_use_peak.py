"""The most blocks of the paged cache that were allocated at once in
the window: the pool's own count, read as each admission's first token
is handed out (allocation happens at admission alone)."""

COUNTERS = []


def read(ctx):
    if "serve" not in ctx:
        return None
    start, end = ctx["serve"]["spans"]["window"]
    seen = [r["blocks_in_use"] for r in ctx["serve"]["requests"]
            if r["stamps"] and start <= r["stamps"][0] < end
            and "blocks_in_use" in r]
    return max(seen) if seen else None
