"""Seconds a rank spent inside the compiled step's ``init_state()``
before the window (the program's span ``hvd: init state``: the
optimizer's state made and the whole state placed on the chips; rank
threads each wait there for the one build, so the counter's sum is
divided by the ranks).  ``None`` for a program without the counter."""

COUNTERS = ["horovod_init_state_seconds_total"]


def read(ctx):
    return ctx["counters"]["window_start"][COUNTERS[0]] / ctx["ranks"] \
        or None
