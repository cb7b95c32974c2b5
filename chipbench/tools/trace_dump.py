#!/usr/bin/env python3
"""Look at a trace by hand, and record a small one for the tests.

    python3 chipbench/run.py --workload <cell> ... --trace 1 --keep-trace 1
    python3 chipbench/tools/trace_dump.py [out_dir]

Writes ``<out_dir>/trace_describe.json`` (every plane and line with its
longest events) and ``<out_dir>/trace_recorded.json`` (the device ops
and the benchmark's annotations of the first ``SECONDS`` of the trace as
``[device, line, name, start_ns, end_ns]``, times from the first
event): the format of ``chipbench/tests/data``.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

SECONDS = 0.7


def main():
    from chipbench import trace_reduce
    from chipbench.run import TRACE_DIR

    out_dir = sys.argv[1] if len(sys.argv) > 1 \
        else os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "trace_describe.json"), "w") as f:
        json.dump(trace_reduce.describe(TRACE_DIR), f, indent=1)
    ops = trace_reduce.load(TRACE_DIR)
    first = min(op.start for op in ops)
    rows = [[op.device, op.line, op.name, round((op.start - first) * 1e9),
             round((op.end - first) * 1e9)]
            for op in sorted(ops, key=lambda op: op.start)
            if op.start - first < SECONDS]
    with open(os.path.join(out_dir, "trace_recorded.json"), "w") as f:
        json.dump(rows, f, separators=(",", ":"))
    print(f"{len(ops)} events, {len(rows)} recorded, into {out_dir}")


if __name__ == "__main__":
    main()
