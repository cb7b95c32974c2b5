"""How long an asynchronous collective of the step program is in flight
a traced step, on the chip where it is longest: the union of the
intervals from each ``start`` event to the end of the next event of its
``pair`` (the report's ``collectives``; ``chipbench/report_time.py``).
The bytes of the ``start`` entries over it are the rate the gradients'
all-reduces get beside the backward pass."""

from chipbench import report_time

COUNTERS = []


def read(ctx):
    return report_time.read_collectives(ctx, report_time.in_flight_ms)
