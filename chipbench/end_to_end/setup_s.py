"""Process start to the first measured step: imports, weights, staging,
compilation or the compile cache's reads, the steps of the correctness
check and the warm-up.  Two spans are not in it, and each run prints
both beside it: the reference's own time, and the start of the device's
runtime (the first ``jax.devices()``), which is the machine's and
drifts by seconds between processes of one tree."""


def read(ctx):
    return ctx["setup_seconds"]
