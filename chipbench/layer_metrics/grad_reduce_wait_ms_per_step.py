"""Device time per traced step, on the chip where it is longest, of the
``done`` halves of the step program's asynchronous collectives (the
report's ``collectives`` of mode ``done``: ``async-collective-done.N``
in a trace): the chip stands at a gradient all-reduce's result, which
is what overlap with the backward pass has not hidden
(``chipbench/report_time.py``)."""

from chipbench import report_time

COUNTERS = []


def read(ctx):
    return report_time.read_collectives(ctx, report_time.mode_ms,
                                        mode="done")
