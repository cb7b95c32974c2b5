"""The flash kernels of the latent-attention layers against the chip's
bf16 peak: the attention FLOPs the traced steps need at the PUBLISHED
widths (``chipbench/kimi_linear_flops.py``: ``Q K^T`` 192 wide and ``P
V`` 128 wide over the causal triangle, forward and backward, nothing
padded; the adapter's ``attention_flops_per_sample``) over the device
time of what lies under ``mla`` and ``flash_fwd`` or ``flash_dkv`` (the
forward and the one backward kernel), over the peak.  The program hands
the kernels values filled up to the keys' width, so the filling reads
here as waste; kernels that take a value width of their own would read
higher by the same yardstick.  The kernels are compute-bound.  A share
over 105% is an error of the count and is refused."""

from chipbench import scope_time

COUNTERS = []


def read(ctx):
    need = getattr(ctx["adapter"], "attention_flops_per_sample", None)
    ms = scope_time.ms_per_step(
        ctx, scope_time.component("mla") + ".*"
        + scope_time.component("flash_fwd", "flash_dkv"))
    if need is None or ms is None:
        return None
    flops = need(ctx["config"], ctx["workload"]) \
        * ctx["window"]["samples_per_step"] / ctx["ranks"]
    share = 100.0 * flops / (ms / 1e3) / ctx["peaks"]["bf16_flops_per_s"]
    if share > 105.0:
        raise ValueError(
            f"mla_flash_roofline reads {share:.1f}%: the attention's "
            "operations are counted too high, or the scopes leave out a "
            "kernel")
    return share
