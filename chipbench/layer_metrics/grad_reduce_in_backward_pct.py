"""The share of the gradient bytes the step program all-reduces that it
reduces inside the backward pass, where each gradient is complete
(``hvd.reduce_in_backward``: the layers of a scanned model), and not
after it: how far the overlap of reduction and backpropagation can
reach at all.  Bytes, known from the program's trace and counted a
program call; nothing of it is timed.

``resnet50-b128-dp4`` does not list it: ResNet's blocks are a Python
loop and no model applies ``reduce_in_backward`` to them, so the whole
tree is reduced after ``value_and_grad`` and this reads 0 there by
construction (what the compiler then moves beside the backward is for
the ``grad_reduce_*`` readers of the device trace to say)."""

from chipbench import scope_join

COUNTERS = ["horovod_step_grad_reduce_bytes_total",
            "horovod_step_grad_reduce_in_backward_bytes_total"]


def read(ctx):
    reduced = scope_join.counter_delta(ctx, COUNTERS[0])
    if reduced <= 0:                # one rank, or a commit without it
        return None
    return 100.0 * scope_join.counter_delta(ctx, COUNTERS[1]) / reduced
