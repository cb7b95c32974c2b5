"""Of the chunks the state-space layers' scans processed in the window
(``horovod_ssm_chunks_total``), the share that went through the Pallas
kernel pair (``horovod_ssm_kernel_chunks_total``: ``ssd_fwd`` /
``ssd_bwd`` of ``horovod_tpu/ops/ssd_kernels.py``) and not through the
XLA form of the scan: 1.0 where every scan's shapes fill whole tiles,
as the published widths do; under 1.0 some layer fell back, and
``ssm_scan_ms_per_step`` is then partly the XLA form's.  Both are the
program's own counts, summed on the device.  A program that declares no
such sum (a commit before the kernels) has no such counter in the
process's registry, which reads an unknown name as 0: nothing is
reported there."""

from chipbench import scope_join

COUNTERS = ["horovod_ssm_chunks_total", "horovod_ssm_kernel_chunks_total"]


def counted(name):
    """Whether this process's program keeps the sum ``name`` at all."""
    from horovod_tpu import telemetry

    return telemetry.registry().get(name) is not None


def read(ctx):
    chunks = scope_join.counter_delta(ctx, COUNTERS[0])
    if chunks <= 0 or not counted(COUNTERS[1]):
        return None
    return scope_join.counter_delta(ctx, COUNTERS[1]) / chunks
