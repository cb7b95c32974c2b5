"""The share of the gradient bytes the step program all-reduces that it
reduces inside the backward pass, where each gradient is complete
(``hvd.reduce_in_backward``: the layers of a scanned model), and not
after it: how far the overlap of reduction and backpropagation can
reach at all.  Bytes, known from the program's trace and counted a
program call; nothing of it is timed."""

from chipbench import scope_join

COUNTERS = ["horovod_step_grad_reduce_bytes_total",
            "horovod_step_grad_reduce_in_backward_bytes_total"]


def read(ctx):
    reduced = scope_join.counter_delta(ctx, COUNTERS[0])
    if reduced <= 0:                # one rank, or a commit without it
        return None
    return 100.0 * scope_join.counter_delta(ctx, COUNTERS[1]) / reduced
