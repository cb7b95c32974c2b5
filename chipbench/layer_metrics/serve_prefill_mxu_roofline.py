"""The prefill programs against the chip's bf16 peak: the forward FLOPs
of the prompts prefilled in the traced span, unpadded and with the
logits of the last position alone (``chipbench/serve_flops.py``), over
the prefill programs' device time, over the peak.  A prefill is
compute-bound from a few hundred tokens on."""

from chipbench import serve_trace

COUNTERS = []


def read(ctx):
    found = serve_trace.runs_in_span(ctx)
    if not found or not found[0]["prefill"]:
        return None
    runs, span = found
    flops, config = ctx["serve"]["flops"], ctx["config"]
    requests = serve_trace.prefilled(ctx, runs, span)
    if not requests:
        return None
    needed = sum(flops.prefill_flops(config, len(r["prompt"]))
                 for r in requests)
    return 100.0 * needed / serve_trace.seconds_of(runs["prefill"]) \
        / ctx["peaks"]["bf16_flops_per_s"]
