"""``chipbench/run.py --trace 1`` in-process, with every leaf operation's
time and path written to ``chiprun_out/ops_<tag>.json`` (ms a traced
step, summed by instruction).  Reads the yardstick, changes none of it.

    python <repo>/tools/traced_ops.py <tag> <cell> <seed>   (cwd = a checkout,
    this one or a parent's unpacked beside it; on the chip)

Each row: [instruction, ms a step, events, op_name path].  ``OPS_OUT`` names
another directory than ``<cwd>/chiprun_out``.  Beside it,
``tables_<tag>.json`` holds the step program's ``renamed`` and
``collectives`` (empty for a checkout whose report has none) and the
seconds the program took to report itself (a line
``{"program_reports_seconds": ...}`` too).
"""
import json
import os
import sys
import time

tag, cell, seed = sys.argv[1:4]
OUT = os.environ.get("OPS_OUT", os.path.join(os.getcwd(), "chiprun_out"))
sys.path.insert(0, os.getcwd())
from chipbench import run, scope_join, trace_reduce  # noqa: E402

orig = scope_join.split


def split(ops, scopes, trace_steps):
    rows = {}
    by_device = trace_reduce.leaf_ops(ops)
    for listed in by_device.values():
        for op in listed:
            path = scopes.get(scope_join.instruction_of(op.name))
            row = rows.setdefault(op.name, [0.0, 0, path])
            row[0] += (op.end - op.start) * 1e3 / trace_steps / len(by_device)
            row[1] += 1
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"ops_{tag}.json"), "w") as f:
        json.dump(sorted(([n] + r for n, r in rows.items()),
                         key=lambda r: -r[1]), f, indent=0)
    return orig(ops, scopes, trace_steps)


scope_join.split = split
find_report = scope_join._find_report


def timed_report(ops):
    t0 = time.perf_counter()
    report = find_report(ops)
    seconds = time.perf_counter() - t0
    print(json.dumps({"program_reports_seconds": seconds}), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"tables_{tag}.json"), "w") as f:
        json.dump({"program_reports_seconds": seconds,
                   "renamed": (report or {}).get("renamed", {}),
                   "collectives": (report or {}).get("collectives", [])},
                  f, indent=0)
    return report


scope_join._find_report = timed_report
args = ["--workload", cell, "--seed", seed, "--seconds", "20", "--trace", "1"]
if os.environ.get("REHEARSE"):
    args += ["--rehearse", "1"]
sys.exit(run.main(args))
