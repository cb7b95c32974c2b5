"""Find a served mix's knee ONCE, on the chip: the steady traffic at a
ladder of rates through one server in one process, and at each rate the
share of the requests that arrived in the window which got their first
token within ``--ttft`` seconds of their scheduled arrival AND held a
mean gap between their tokens under ``--gap`` seconds (DistServe's
attainment of both limits, arXiv:2401.09670).  The knee is the highest
rate at which that share is at least ``--share`` on every seed.  The
readings go into the mix files beside the rates they fixed; no run of
the benchmark searches for a rate.

    python3 chipbench/tools/sweep_rate.py --workload <cell> \
        --rates 2,3,4,5,6 --seeds 1,2,3 --seconds 20 --out chiprun_out/sweep.jsonl

The weights are those of the first seed for every point (a rate's
capacity does not depend on them); the traffic's order is each seed's.
"""

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def attainment(requests, span, ttft, gap):
    """(share that met both limits, the readings) over the requests
    that arrived in ``span``; one that failed misses."""
    from chipbench.serve_run import is_failed

    met, waits, gaps = [], [], []
    for r in requests:
        if not span[0] <= r["due"] < span[1]:
            continue
        if is_failed(r):
            met.append(False)
            continue
        wait = r["stamps"][0] - r["due"]
        mean_gap = (r["stamps"][-1] - r["stamps"][0]) \
            / max(len(r["stamps"]) - 1, 1)
        waits.append(wait)
        gaps.append(mean_gap)
        met.append(wait <= ttft and mean_gap <= gap)

    def p(values, q):
        return statistics.quantiles(values, n=10, method="inclusive")[q - 1] \
            if len(values) > 1 else None
    return sum(met) / max(len(met), 1), {
        "arrived": len(met), "ttft_s_p50": p(waits, 5),
        "ttft_s_p90": p(waits, 9), "mean_gap_s_p50": p(gaps, 5),
        "mean_gap_s_p90": p(gaps, 9)}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seeds", default="1,2,3")
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--ttft", type=float, default=2.0)
    parser.add_argument("--gap", type=float, default=0.15)
    parser.add_argument("--share", type=float, default=0.9)
    parser.add_argument("--rehearse", type=int, default=0)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    seeds = [int(s) for s in args.seeds.split(",")]

    import time

    from chipbench import serve_run, weights

    c = serve_run.Served(args.workload, args.rehearse)
    server, _ = c.serve(weights.seed_key(seeds[0]))
    found = []
    for rate in rates:
        for seed in seeds:
            load = serve_run.Load(server)
            requests, _ = c.traffic(seed, args.seconds, False, rate)
            t_open = load.offer(requests, c.workload["warmup_seconds"])
            load.finish(c.workload["drain_seconds"])
            span = (t_open, t_open + args.seconds)
            share, readings = attainment(requests, span, args.ttft, args.gap)
            record = serve_run.window_record(c.config, c.flops, requests,
                                             span)
            lateness = [r["sent"] - r["due"] for r in requests]
            point = {"rate": rate, "seed": seed, "attainment": share,
                     **readings,
                     "output_tokens_per_s": record["emitted"] / args.seconds,
                     "gap_s_p90": serve_run.percentile(record["gaps"], 90)
                     if record["gaps"] else None,
                     "failed": sum(map(serve_run.is_failed, requests)),
                     "drain_s": time.perf_counter() - span[1],
                     "lateness_worst_ms": 1e3 * max(lateness)}
            found.append(point)
            print(json.dumps(point), flush=True)
            if args.out:
                with open(os.path.join(ROOT, args.out), "a") as f:
                    f.write(json.dumps(point) + "\n")
    server.stop()
    c.close()
    held = [rate for rate in rates if all(
        p["attainment"] >= args.share for p in found if p["rate"] == rate)]
    print(json.dumps({"knee": max(held) if held else None,
                      "limits": {"ttft_s": args.ttft, "mean_gap_s": args.gap,
                                 "share": args.share},
                      "rates_that_held": held}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
