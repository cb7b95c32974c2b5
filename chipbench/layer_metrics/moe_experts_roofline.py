"""The routed experts' grouped products against the chip's bf16 peak:
the FLOPs the held assignments needed (18 x hidden x width an
assignment, forward and backward, no credit for the backward pass's
recomputation of gate and up; ``chipbench/afmoe_flops.py``) over the
device time under the scope ``experts`` (the grouped products and their
activation), over the peak.  The assignments are the program's own count
(``horovod_moe_held_assignments_total``, summed on the device) as a mean
over the window's steps: the traced steps follow the window on the same
batch.  Compute-bound: 1,024 rows an expert against a 2048 x 1024
matrix."""

from chipbench import afmoe_flops, scope_join, scope_time

COUNTERS = ["horovod_moe_held_assignments_total"]


def read(ctx):
    held = scope_join.counter_delta(ctx, COUNTERS[0])
    ms = scope_time.ms_per_step(ctx, scope_time.under("moe", "experts"))
    if held <= 0 or ms is None:
        return None
    flops = afmoe_flops.grouped_products_train_flops_per_assignment(
        ctx["config"]) * held / ctx["window"]["steps"] / ctx["ranks"]
    return 100.0 * flops / (ms / 1e3) / ctx["peaks"]["bf16_flops_per_s"]
