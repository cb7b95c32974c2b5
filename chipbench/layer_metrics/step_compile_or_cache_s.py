"""Seconds before the window that first calls of the compiled programs
spent in the backend compiler or reading the persistent compile cache:
the part of ``compile_s`` that a warm cache turns from a compile into a
read."""

COUNTERS = ["horovod_compile_backend_seconds_total",
            "horovod_compile_cache_read_seconds_total"]


def read(ctx):
    start = ctx["counters"]["window_start"]
    return sum(start[name] for name in COUNTERS) or None
