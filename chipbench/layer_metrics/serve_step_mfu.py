"""The whole served span's share of the chip's bf16 peak: the forward
FLOPs of every prompt prefilled and every token decoded in the traced
span (``chipbench/serve_flops.py``; no padding, no masked slot) over the
span's seconds, over the peak.  It bounds what the prefill and decode
rooflines can show end to end."""

from chipbench import serve_trace

COUNTERS = []


def read(ctx):
    span = serve_trace.traced_span(ctx)
    if span is None:
        return None
    flops, config = ctx["serve"]["flops"], ctx["config"]
    host = serve_trace.host_span(span)
    needed = sum(flops.prefill_flops(config, len(r["prompt"]))
                 for r, _ in serve_trace.stamps_in(ctx, host, first=True)) \
        + sum(flops.decode_flops(config, len(r["prompt"]) + i - 1)
              for r, i in serve_trace.stamps_in(ctx, host, first=False))
    return 100.0 * needed / (span[1] - span[0]) / ctx["chips"] \
        / ctx["peaks"]["bf16_flops_per_s"]
