"""Of the decode ticks the window dispatched
(``horovod_serve_decode_ticks_total``), the share whose attention read
the paged cache in place through the Pallas kernel
(``horovod_serve_paged_kernel_ticks_total``:
``paged_decode_attention`` of ``horovod_tpu/ops/paged_kernels.py``) and
not through the XLA form's gathered block views: 1.0 where the process
computes on a TPU and the cache's shapes fill the kernel's tiles, as
the served configuration's do; 0.0 where the programs took the XLA
form, and ``serve_decode_device_ms_per_tick`` is then that form's.
``PagedKVPrograms.decode`` counts both on the host at dispatch (the
choice is static a process).  A program that counts no decode ticks (a
commit before the kernel) has neither counter in the process's
registry, which reads an unknown name as 0: nothing is reported
there."""

from chipbench import scope_join

COUNTERS = ["horovod_serve_decode_ticks_total",
            "horovod_serve_paged_kernel_ticks_total"]


def counted(name):
    """Whether this process's program keeps the counter ``name`` at
    all."""
    from horovod_tpu import telemetry

    return telemetry.registry().get(name) is not None


def read(ctx):
    ticks = scope_join.counter_delta(ctx, COUNTERS[0])
    if ticks <= 0 or not counted(COUNTERS[1]):
        return None
    return scope_join.counter_delta(ctx, COUNTERS[1]) / ticks
