"""Seconds inside ``hvd.init()`` before the window (the program's span
``hvd: init``: the engine, its executor over the chips, the registry;
once a process, ``hvd.run`` calls it before the rank threads start).
``None`` for a program without the counter."""

COUNTERS = ["horovod_init_seconds_total"]


def read(ctx):
    return ctx["counters"]["window_start"][COUNTERS[0]] or None
