"""Device time per traced step of the flash-attention backward kernel
(the Pallas calls under the scope ``flash_dkv``), the mean over the
chips (``chipbench/scope_join.py``).  Since PR 28 this is the WHOLE
backward: one kernel yields dq, dk and dv under the scope and the name
the older dk/dv kernel had (there is no ``flash_dq`` kernel any more, and
no reader of it since PR 41); beside a ledger line from before PR 28 it
compares with that line's ``flash_dq_ms_per_step`` +
``flash_dkv_ms_per_step``."""

from chipbench import scope_join

COUNTERS = []


def read(ctx):
    return scope_join.read(ctx, "kernel", "flash_dkv")
