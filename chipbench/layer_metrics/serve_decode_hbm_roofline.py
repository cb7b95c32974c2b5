"""The decode programs against the chip's memory bandwidth: the bytes
the traced span's ticks cannot avoid (the weights once a tick, and once
the cached rows each token decoded in the span attends:
``chipbench/serve_flops.py``, from the requests alone) over the
published bytes a second, over the decode programs' device time.
Decoding is on the HBM side of the roofline: a tick's FLOPs at 32 slots
need a twentieth of the time its bytes do."""

from chipbench import serve_trace

COUNTERS = []


def read(ctx):
    found = serve_trace.runs_in_span(ctx)
    if not found or not found[0]["decode"]:
        return None
    runs, span = found
    flops, config = ctx["serve"]["flops"], ctx["config"]
    tokens = serve_trace.stamps_in(
        ctx, serve_trace.host_span(span), first=False)
    needed = len(runs["decode"]) * flops.weight_bytes(config) + sum(
        flops.decode_kv_bytes(config, len(r["prompt"]) + i - 1)
        for r, i in tokens)
    return 100.0 * needed / ctx["peaks"]["hbm_bytes_per_s"] \
        / serve_trace.seconds_of(runs["decode"])
