"""Device time per traced step of the flash-attention forward kernel
(the Pallas calls under the scope ``flash_fwd``), the mean over the chips
(``chipbench/scope_join.py``)."""

from chipbench import scope_join

COUNTERS = []


def read(ctx):
    return scope_join.read(ctx, "kernel", "flash_fwd")
