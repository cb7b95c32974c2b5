"""Programs the compiled-step cache had to build inside the window
(expected 0)."""

COUNTERS = ["horovod_program_cache_misses_total"]


def read(ctx):
    name = COUNTERS[0]
    return ctx["counters"]["window_end"][name] \
        - ctx["counters"]["window_start"][name]
