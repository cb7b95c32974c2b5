"""Samples trained per second per chip over the whole window: the
samples of all its steps over the host's time from the first step's
enqueue to the last step's result, over the chips.  A sample is what
the mix counts (``samples_per_row``): a token, an image."""


def read(ctx):
    return ctx["window"]["samples_per_second"] / ctx["chips"]
