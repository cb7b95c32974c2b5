"""Device time per traced step, the mean over the chips, of everything
the step program puts under ``hvd_step/grad_reduce`` and
``hvd_step/aux_reduce`` (``scope_join``'s phase ``reduce``, which with
the forward, backward, remat, optimizer and unattributed phases adds up
to the chip's busy time): the synchronous all-reduces (small leaves the
compiler combined), the ``start`` and the ``done`` halves of the
asynchronous ones, the loss's and the model state's means.  The
compute fusions that carry an asynchronous all-reduce through the
backward pass are booked where their compute belongs, not here
(``grad_reduce_carried_ms_per_step``)."""

from chipbench import scope_join

COUNTERS = []


def read(ctx):
    return scope_join.read(ctx, "phase", "reduce")
