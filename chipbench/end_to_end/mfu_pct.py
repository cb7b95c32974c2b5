"""Model FLOP/s utilization: the operations forward and backward need
per sample (chipbench/flops.py, recomputation not counted) times the
samples per second, over chips times the published bf16 peak of the
device.  Over 105% the count is wrong."""


def read(ctx):
    return 100.0 * ctx["window"]["samples_per_second"] \
        * ctx["flops_per_sample"] / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
