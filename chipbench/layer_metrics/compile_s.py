"""Seconds the program spent in first calls of its compiled programs
(compilation, or reading the persistent cache) before the window."""

COUNTERS = ["horovod_compile_seconds_total"]


def read(ctx):
    return ctx["counters"]["window_start"]["horovod_compile_seconds_total"]
