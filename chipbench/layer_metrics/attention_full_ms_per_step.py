"""Device time of the ``full_attention`` layers' attention per traced
step (``full_attention/.../attn``: projections, head norms, gate and the
flash kernels under the plain causal mask; forward, backward and
recomputation), the mean over the chips.  ``attention_ms_per_step`` less
this is the sliding layers (``chipbench/scope_time.py``)."""

from chipbench import scope_time

COUNTERS = []


def read(ctx):
    return scope_time.ms_per_step(
        ctx, scope_time.under("full_attention", "attn"))
