"""``flash_roofline`` for a step that may hold other Pallas kernels: the
attention FLOPs the traced steps need, from shapes and each layer's mask
(the adapter's ``attention_flops_per_sample``), over the summed device
time of the kernels under the scopes ``flash_fwd`` and ``flash_dkv``
(the forward and the one backward kernel: ``scope_join.KERNELS``), over
the chip's bf16 peak.
The kernels are compute-bound."""

from chipbench import scope_join

COUNTERS = []


def read(ctx):
    need = getattr(ctx["adapter"], "attention_flops_per_sample", None)
    found = scope_join.split_of(ctx)
    if found is None or need is None:
        return None
    ms = sum(found["kernel"].values())
    if ms <= 0:
        return None
    flops = need(ctx["config"], ctx["workload"]) \
        * ctx["window"]["samples_per_step"] / ctx["ranks"]
    return 100.0 * flops / (ms / 1e3) / ctx["peaks"]["bf16_flops_per_s"]
