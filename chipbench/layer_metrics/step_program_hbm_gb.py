"""What the step program's own account says it holds of a chip's memory
(the compiled program's ``memory_analysis()``): arguments + outputs +
temporaries - the outputs that alias donated arguments + code.  Beside
``memory_peak_bytes``, which samples the runtime from outside."""

from chipbench import scope_join

COUNTERS = []


def read(ctx):
    report = scope_join.report_of(ctx)
    if report is None:
        return None
    m = report["memory"]
    return (m["argument"] + m["output"] + m["temp"] - m["alias"]
            + m["generated_code"]) / 1e9
