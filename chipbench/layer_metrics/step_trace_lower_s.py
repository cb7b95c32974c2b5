"""Seconds before the window that first calls of the compiled programs
spent tracing to a jaxpr and lowering it to MLIR: the part of
``compile_s`` that a warm compile cache does not save."""

COUNTERS = ["horovod_compile_trace_seconds_total",
            "horovod_compile_lower_seconds_total"]


def read(ctx):
    start = ctx["counters"]["window_start"]
    return sum(start[name] for name in COUNTERS) or None
