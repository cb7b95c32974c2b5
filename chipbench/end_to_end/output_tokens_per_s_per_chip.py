"""Tokens handed out in the window (every request's, the first ones
included) over its seconds, over the chips.  Judged in the saturated
cell alone, whose mix replays one schedule (``order_seed``): with the
seed drawing the order a 20 s window spread by 4.6 to 5.2% from seed to
seed, the head of the queue being other requests each time, where two
runs of one order agree to 0.03-1.1% (PERF.md, PR 42)."""


def read(ctx):
    return ctx["window"]["steps"] / ctx["window"]["seconds"] / ctx["chips"]
