"""Device time per traced step, the mean over the chips, of the routed
experts' grouped-matmul kernels that run in the forward pass: the three
products a layer that the model asks for (gate, up, down).  The kernels
are the instructions the step program's report lists as ``renamed``
under a compiler's name that starts ``ragged-dot`` (the TPU compiler's
kernel for ``lax.ragged_dot`` and its metadata call); the phase is that
of the path the program recovered for each (``chipbench/report_time.py``)."""

from chipbench import report_time

COUNTERS = []


def read(ctx):
    return report_time.read_renamed(ctx, "ragged-dot", ("forward",))
