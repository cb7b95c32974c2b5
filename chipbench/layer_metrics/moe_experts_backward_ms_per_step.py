"""Device time per traced step, the mean over the chips, of the routed
experts' grouped-matmul kernels that run in the backward pass: a layer's
three gradients to its products' inputs and its three weight gradients,
six kernels to the forward's three.  The backward runs no product of
the forward again since PR 37 (a layer's first pass hands it the gate
and up products); only a pass beyond a layer's first is still
recomputed there, and its kernels are booked here too (phases
``backward`` and ``remat``; ``moe_experts_forward_ms_per_step`` says
which instructions; ``chipbench/report_time.py``)."""

from chipbench import report_time

COUNTERS = []


def read(ctx):
    return report_time.read_renamed(ctx, "ragged-dot",
                                    ("backward", "remat"))
