"""Share of the leaf operations' device time that the join books to no
phase of the step: operations whose instruction the step program's
table does not hold, or whose path lies under no ``hvd_step/`` scope.
The honesty of forward, backward, remat, optimizer and the reduction
(``chipbench/scope_join.py``)."""

from chipbench import scope_join

COUNTERS = []


def read(ctx):
    found = scope_join.split_of(ctx)
    if found is None or found["total"] <= 0:
        return None
    return 100.0 * found["phase"]["unattributed"] / found["total"]
