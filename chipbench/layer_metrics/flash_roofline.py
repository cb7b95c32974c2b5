"""The flash-attention kernels (the forward, and the one backward kernel
that yields dq, dk and dv) against the chip's bf16 peak: the attention
FLOPs the traced steps need, from shapes, over the kernels' summed
device time, over the peak.  The kernels are compute-bound: at these
sizes the FLOP bound is above the HBM bound.

The trace names a Pallas kernel after the flax module it sits in
(``attn.21``), not after the kernel, so this takes every Pallas kernel
of the step: in the cells that list this reader those are the two
flash kernels and nothing else (chip_smoke.py counts them).  A PR that
adds another kernel to an LM step has to give its kernels names the
trace shows, and a reader of their own."""

from chipbench import trace_reduce

COUNTERS = []
KERNELS = r"\[tpu_custom_call\]$"


def read(ctx):
    need = getattr(ctx["adapter"], "attention_flops_per_sample", None)
    if not ctx.get("trace") or need is None:
        return None
    seconds = trace_reduce.matching_seconds(ctx["trace"], KERNELS)
    worst = max(seconds.values())
    if worst <= 0:
        return None
    flops = need(ctx["config"], ctx["workload"]) \
        * ctx["window"]["samples_per_step"] / ctx["ranks"] \
        * ctx["trace_steps"]
    return 100.0 * flops / worst / ctx["peaks"]["bf16_flops_per_s"]
