#!/usr/bin/env python
"""``ci.sh perf``: the counts a CPU run can decide exactly.

Runs the collective_bench, lm_bench and ckpt_bench legs below and
compares what they COUNT against ``benchmarks/BASELINE.json``: the
codec's wire-byte ratios (3.97x int8, 7.88x int4), what each hop of the
2 x 2 decomposition moves per 8 MiB call, bitwise parity and zero
steady-state recompiles of the bucketized reduction, every async
checkpoint save ending anchored, and the MoE leg's loss gap, recompiles
and alltoall wire ratio.  No metric here is a wall-clock time, a rate or
a ratio of two times: speed is measured on the chip (``chipbench/``,
``PERF.md``).

The counts are deterministic: they move only when someone changes the
codec or the accounting, so an ``eq`` band is tight and TWO-SIDED (bytes
disappearing from a hop counter is as much an accounting regression as
bytes appearing).  The same matrix then runs under a seeded fault plan
and is held to the same bands: faults never change what the wire moves.

``--update-baseline`` re-records the measured values (the tolerance
spec lives here in code, the values in the JSON); use it after an
intentional change of the codec or the accounting.
"""

import argparse
import json
import math
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_PATH = os.path.join(REPO, "benchmarks", "BASELINE.json")

BENCHES = {
    "wire": ["benchmarks/collective_bench.py", "--np", "4", "--cpu",
             "--wire-dtype", "all", "--iters", "6"],
    "pair": ["benchmarks/collective_bench.py", "--np", "4", "--cpu",
             "--wire-pair", "all", "--iters", "6"],
    # bucket-granular dispatch against the grouped program on the
    # compiled path (lm_bench's --overlap-compare drives
    # CompiledGroupedAllreduce under hvd.run rank threads — the SPMD
    # step bypasses it)
    "overlap": ["benchmarks/lm_bench.py", "--cpu", "4",
                "--parallelism", "2,2,1", "--d-model", "64",
                "--layers", "4", "--overlap-compare", "--iters", "8",
                "--warmup", "2", "--overlap-bucket-bytes", "524288"],
    # async CRC-anchored checkpointing at the default cadence/payload
    # (docs/data.md)
    "ckpt": ["benchmarks/ckpt_bench.py", "--steps", "60"],
    # expert parallelism: capacity-routed MoE vs its dense-FLOP-
    # matched baseline on identical data (the loss-parity gate), plus
    # the quantized alltoall wire scrape the expert dispatch rides
    "moe": ["benchmarks/lm_bench.py", "--cpu", "1", "--moe-experts",
            "8", "--moe-topk", "2", "--moe-capacity-factor", "1.25",
            "--d-model", "64", "--layers", "2", "--heads", "4",
            "--seq", "128", "--batch", "4", "--iters", "12",
            "--warmup", "2"],
}

#: The seeded fault plan the matrix ALSO runs under.  Non-terminal
#: faults only — the matrix must complete — but real ones: fabric delays and 5xx bursts exercise the
#: retry/backoff path, the probabilistic slow_rank makes one rank a
#: straggler mid-sweep.  Deterministic by seed.
FAULT_PLAN = {"seed": 20260804, "events": [
    {"kind": "delay_ms", "proc": 0, "ms": 25,
     "after_requests": 10, "count": 6},
    {"kind": "http_error", "proc": 1, "code": 503,
     "after_requests": 12, "count": 3},
    {"kind": "slow_rank", "rank": 2, "ms": 15,
     "after_collectives": 6, "count": 4, "p": 0.7},
]}

# metric -> (bench, extractor, direction, relative tolerance,
#            absolute bound or None).  direction 'eq': measured must
#  stay WITHIN baseline*(1±tol), a drift in EITHER direction means
#  the codec or the accounting changed; 'max': measured must stay
#  BELOW baseline*(1+tol).  The absolute bound is independent of the
#  recorded baseline: a floor for 'eq' (the ratios are
#  higher-is-better), a ceiling for 'max'.
METRICS = {
    # codec wire ratios — deterministic byte accounting
    "wire_int8_reduction_vs_f32": (
        "wire",
        lambda d: d["wire_f32_engine_wire_bytes"]
        / d["wire_int8_engine_wire_bytes"],
        "eq", 0.03, 3.8),
    "wire_int4_reduction_vs_f32": (
        "wire",
        lambda d: d["wire_f32_engine_wire_bytes"]
        / d["wire_int4_engine_wire_bytes"],
        "eq", 0.03, 7.5),
    # per-hop cross/inner budgets — deterministic accounting of what
    # each hop moves per 8 MiB call (the decomposition's whole point)
    "pair_f32_int8_cross_bytes": (
        "pair", lambda d: d["pair_f32_int8_cross_bytes"],
        "eq", 0.05, None),
    "pair_f32_int4_cross_bytes": (
        "pair", lambda d: d["pair_f32_int4_cross_bytes"],
        "eq", 0.05, None),
    "pair_bf16_int4_inner_bytes": (
        "pair", lambda d: d["pair_bf16_int4_inner_bytes"],
        "eq", 0.05, None),
    # bucket-granular dispatch on the compiled path.  Steady state
    # must never recompile: bucket programs land in the shared cache
    # during warmup, and a later miss on ANY rank is a latch/keying
    # bug — exact, fault plan included
    "overlap_steady_recompiles": (
        "overlap", lambda d: d["overlap_steady_recompiles"],
        "max", 0.0, 0.0),
    # bucketized dispatch is the SAME math: per-rank results bitwise
    # vs the grouped program, clean and faulted
    "overlap_bitwise_parity": (
        "overlap", lambda d: d["overlap_bitwise_parity"],
        "eq", 0.0, 1.0),
    # async checkpointing: hiding the write must never mean losing
    # it — every async save at the bench cadence must end
    # journaled-anchored, exact
    "ckpt_async_anchored_frac": (
        "ckpt", lambda d: d["ckpt_async_anchored_frac"],
        "eq", 0.0, 1.0),
    # expert parallelism (fused quantized alltoall PR).  The loss gap
    # vs the dense-FLOP-matched baseline carries the <=1% acceptance
    # bar as an absolute ceiling; the relative band is wide because
    # tiny-model losses wobble with bf16 reduction order
    "moe_loss_gap": (
        "moe", lambda d: d["moe_loss_gap"], "max", 4.0, 0.01),
    # fixed-capacity dispatch means static shapes: the steady state
    # must never re-enter XLA — exact, fault plan included
    "moe_steady_recompiles": (
        "moe", lambda d: d["moe_steady_recompiles"],
        "max", 0.0, 0.0),
    # the dispatch wire's int8 codec ratio — deterministic byte
    # accounting scraped from horovod_alltoall_*_bytes_total, same
    # band and floor as the reduction wire's
    "moe_alltoall_int8_ratio": (
        "moe", lambda d: d["moe_alltoall_int8_ratio"],
        "eq", 0.03, 3.8),
}


def run_bench(args_list, fault_plan=None):
    """Run one bench invocation, return its JSON row (the last
    stdout line).  With ``fault_plan``, the whole invocation runs
    under the seeded plan (workers inherit HOROVOD_FAULT_PLAN through
    the launcher's env handoff)."""
    cmd = [sys.executable] + args_list
    env = dict(os.environ)
    tag = ""
    if fault_plan is not None:
        env["HOROVOD_FAULT_PLAN"] = json.dumps(fault_plan)
        tag = " [under fault plan]"
    print(f"[perf] running: {' '.join(args_list)}{tag}", flush=True)
    out = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True,
                         timeout=900, env=env)
    if out.returncode != 0:
        sys.stderr.write(out.stdout[-4000:] + out.stderr[-4000:])
        raise RuntimeError(f"bench failed: {' '.join(args_list)}{tag}")
    for line in reversed(out.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError("bench produced no JSON row")


def _measure(fault_plan=None):
    results = {name: run_bench(args, fault_plan=fault_plan)
               for name, args in BENCHES.items()}
    measured = {}
    for metric, (bench, extract, *_rest) in METRICS.items():
        try:
            measured[metric] = round(float(extract(results[bench])), 3)
        except KeyError:
            pass        # _gate reports what the run did not print
    return measured


def _gate(measured, baseline, tag="perf"):
    """Compare one leg against the baseline; returns the metrics out
    of band.  The faulted leg (``tag="fault"``) is held to the same
    bands as the clean one."""
    failures = []
    for metric, (_bench, _x, direction, tol, bound) in METRICS.items():
        got = measured.get(metric)
        if got is None:
            print(f"[{tag}] FAIL {metric}: the run did not print it")
            failures.append(metric)
            continue
        base = baseline.get(metric)
        lines = [f"{metric}: measured {got}"]
        ok = True
        if base is not None:
            lo = base * (1 - tol) if direction == "eq" else -math.inf
            hi = base * (1 + tol)
            ok = lo <= got <= hi
            lines.append(f"baseline {base} (must stay within "
                         f"[{lo:.3f}, {hi:.3f}])" if direction == "eq"
                         else f"baseline {base} (must stay <= {hi:.3f})")
        if bound is not None:
            ok = ok and (got >= bound if direction == "eq"
                         else got <= bound)
            lines.append(f"absolute bar {bound}")
        status = "ok  " if ok else "FAIL"
        print(f"[{tag}] {status} {' | '.join(lines)}")
        if not ok:
            failures.append(metric)
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--update-baseline", action="store_true",
                    help="record the measured values as the new "
                         "baseline instead of gating")
    ap.add_argument("--baseline", default=BASELINE_PATH)
    ap.add_argument("--no-fault-plan", action="store_true",
                    help="skip the second matrix pass under the "
                         "seeded fault plan (the clean gate only)")
    opts = ap.parse_args()

    measured = _measure()

    if opts.update_baseline:
        payload = {
            "_comment": "perf-gate baseline (tools/perf_gate.py; "
                        "ci.sh perf).  Values only — the tolerance "
                        "band and absolute bounds live in the gate's "
                        "METRICS table.",
            "metrics": measured,
        }
        with open(opts.baseline, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"[perf] baseline updated: {opts.baseline}")
        for k, v in sorted(measured.items()):
            print(f"[perf]   {k} = {v}")
        return 0

    with open(opts.baseline) as f:
        baseline = json.load(f)["metrics"]

    failures = _gate(measured, baseline)
    if not opts.no_fault_plan:
        # the same matrix, under the seeded fault plan: the benches
        # must COMPLETE (retry/recovery works) and move the exact
        # same bytes
        faulted = _measure(fault_plan=FAULT_PLAN)
        failures += [f"fault:{m}" for m in
                     _gate(faulted, baseline, tag="fault")]

    if failures:
        print(f"[perf] REGRESSION: {len(failures)} metric(s) out of "
              f"band: {', '.join(failures)} — if intentional, rerun "
              "with --update-baseline and commit the new "
              "benchmarks/BASELINE.json")
        return 1
    print("[perf] gate green (clean matrix"
          + (")" if opts.no_fault_plan
             else " + matrix under the seeded fault plan)"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
