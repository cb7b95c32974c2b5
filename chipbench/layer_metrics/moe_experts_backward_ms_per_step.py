"""Device time per traced step, the mean over the chips, of the routed
experts' grouped-matmul kernels that run in the backward pass, a
recomputation included: a layer's three products run again, the three
gradients to their inputs, the three weight gradients, nine kernels to
the forward's three (``moe_experts_forward_ms_per_step`` says which
instructions; ``chipbench/report_time.py``)."""

from chipbench import report_time

COUNTERS = []


def read(ctx):
    return report_time.read_renamed(ctx, "ragged-dot",
                                    ("backward", "remat"))
