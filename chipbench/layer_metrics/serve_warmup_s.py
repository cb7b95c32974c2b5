"""Seconds of a served run's set-up from the seed to a server that
ticks: the weights made on the device in the served type and
``PagedKVPrograms.warmup`` (every prefill, ingest and decode program
compiled, or read from the compile cache, and run once)."""

COUNTERS = []


def read(ctx):
    if "serve" not in ctx:
        return None
    return ctx["serve"]["setup_phases"]["weights_and_programs_warmup"]
