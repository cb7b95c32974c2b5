"""Device time under ``mla`` per traced step (a latent-attention layer's
mixer: the query, latent and expansion projections, the latent's norm,
the flash kernels, the output projection; forward, backward and
recomputation), the mean over the chips (``chipbench/scope_time.py``)."""

from chipbench import scope_time

COUNTERS = []


def read(ctx):
    return scope_time.ms_per_step(ctx, scope_time.component("mla"))
