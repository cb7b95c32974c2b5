"""The routed layers' load-balancing loss ``E sum_e f_e P_e`` in the
window, a layer a step a rank (``horovod_moe_aux_loss_total``, summed on
the device inside the step in steps of 2^-8): 1.0 under a balanced
router, up to the number of experts under one that sends every token to
one.  It describes the router; training moves it, no change of the
program should."""

from chipbench import scope_join

COUNTERS = ["horovod_moe_aux_loss_total"]


def read(ctx):
    total = scope_join.counter_delta(ctx, COUNTERS[0])
    if total <= 0:                  # a program without the sum
        return None
    return total / ctx["window"]["steps"] / ctx["ranks"] \
        / ctx["config"]["num_hidden_layers"]
