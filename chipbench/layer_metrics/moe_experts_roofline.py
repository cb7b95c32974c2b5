"""The routed experts' grouped products against the chip's bf16 peak:
the FLOPs the held assignments needed (18 x hidden x width an
assignment: three products forward, six gradients backward, none run
twice; ``chipbench/afmoe_flops.py``) over the device time of the
grouped products and their activation, over the peak.  That time is
what lies under the scopes ``moe`` and ``experts`` (the activation
between the products) plus the grouped-product kernels themselves,
which the compiler names after itself (``ragged-dot-none``): the
program's report lists them as ``renamed`` with a path recovered from
their users (``moe_experts_forward_`` + ``..._backward_ms_per_step``).
A recovered path may or may not hold ``experts`` (8 of SmallThinker's
48 do, none of Trinity's), so the scopes are read from the table
without the listed kernels and every kernel is counted once.
The assignments are the program's own count
(``horovod_moe_held_assignments_total``, summed on the device) as a mean
over the window's steps: the traced steps follow the window on the same
batch.  Compute-bound: 1,024 rows an expert against a 2048 x 1024
matrix."""

from chipbench import afmoe_flops, report_time, scope_join, scope_time

COUNTERS = ["horovod_moe_held_assignments_total"]


def read(ctx):
    held = scope_join.counter_delta(ctx, COUNTERS[0])
    report = report_time.report_with(ctx, "renamed")
    if held <= 0 or report is None:
        return None
    scopes, renamed = report["scopes"], report["renamed"]
    ms = report_time.renamed_ms(
        ctx["trace"], scopes, renamed, ctx["trace_steps"], "ragged-dot",
        ("forward", "backward", "remat"))
    if ms <= 0:
        return None
    ms += scope_time.split_ms(
        ctx["trace"], {k: v for k, v in scopes.items() if k not in renamed},
        ctx["trace_steps"], {"it": scope_time.under("moe", "experts")})["it"]
    flops = afmoe_flops.grouped_products_train_flops_per_assignment(
        ctx["config"]) * held / ctx["window"]["steps"] / ctx["ranks"]
    return 100.0 * flops / (ms / 1e3) / ctx["peaks"]["bf16_flops_per_s"]
