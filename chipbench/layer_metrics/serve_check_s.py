"""Seconds of a served run's set-up in the check: the mix's fixed
requests through the server that the window then uses, from the first
``submit`` to the last cached row read back."""

COUNTERS = []


def read(ctx):
    if "serve" not in ctx:
        return None
    return ctx["serve"]["setup_phases"]["check"]
