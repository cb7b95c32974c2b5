"""What a served cell's limits are set from, read in ONE process on the
chip: for every seed the numbers a run compares (the program through
the cell's own server, check and a short window at the cell's load,
against the reference), and for the first ``--control`` seeds the same
numbers of the control: the reference computed in fp8 put in the
program's place, at each position of the same prompts and tokens (its
cache against the float32 reference's, and the float32 reference's
margin of the token fp8 puts first).

    python3 chipbench/tools/serve_calibrate.py --workload <cell> \
        --seeds 1,2,... --control 3 --seconds 12 --out chiprun_out/cal.jsonl

``--dtype`` serves another type than the configuration's and
``--chip-limits 1`` judges a rehearsal by the limits of the chip's runs
(the CPU test reads bfloat16 at the rehearsal sizes, which rehearse in
float32 under limits of their own).
"""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def compared(c, *inputs):
    """(the numbers a run compares, whether the cell's limits pass them),
    judged by the harness's own ``Compare``."""
    from chipbench import run as harness
    from chipbench import serve_run

    compare = harness.Compare()
    serve_run.compare_with_reference(compare, c.limits, *inputs)
    return {name: value for name, (value, _) in compare.numbers.items()}, \
        compare.ok


def control_requests(requests, outputs):
    """The control in the program's place: per request the fp8
    reference's cache, and the token it puts first at each position."""
    out = []
    for request, got in zip(requests, outputs):
        stand_in = {"prompt": request["prompt"],
                    "tokens": [int(t) for t in got["logits"].argmax(-1)]}
        if "cache" in request:
            stand_in["cache"] = {"k": got["k"], "v": got["v"]}
        out.append(stand_in)
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control", type=int, default=3)
    parser.add_argument("--seconds", type=float, default=12.0)
    parser.add_argument("--rehearse", type=int, default=0)
    parser.add_argument("--dtype", default=None)
    parser.add_argument("--chip-limits", type=int, default=0)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    from chipbench import serve_run, weights

    changes = {"torch_dtype": args.dtype} if args.dtype else None
    c = serve_run.Served(args.workload, args.rehearse, changes)
    if args.chip_limits:
        c.limits = serve_run.harness.load_json(
            c.bench["paths"][0], "limits", args.workload + ".json")["limits"]
    for n, seed in enumerate(seeds):
        key = weights.seed_key(seed)
        server, journal = c.serve(key)
        load = serve_run.Load(server)
        checked = c.check(load, journal, seed)
        requests, _ = c.traffic(seed, args.seconds, False)
        t_open = load.offer(requests, c.workload["warmup_seconds"])
        load.finish(c.workload["drain_seconds"])
        span = (t_open, t_open + args.seconds)
        sampled = serve_run.window_sample(
            requests, span, c.workload["window_check_requests"], seed)
        lateness = max(r["sent"] - r["due"] for r in requests)
        failed = sum(map(serve_run.is_failed, checked + requests))
        server.stop()
        del server, load
        gc.collect()
        whole, outputs = serve_run.follow_reference(c, key, checked, sampled)
        sound, sound_ok = compared(c, whole, sampled, outputs)
        point = {"seed": seed, "sound": sound, "sound_correct": sound_ok,
                 "failed": failed, "sampled": len(sampled),
                 "generator_lateness_worst_ms": 1e3 * lateness}
        if n < args.control:
            _, lower = serve_run.follow_reference(c, key, checked, sampled,
                                                  "fp8")
            stand_ins = control_requests(whole + sampled, lower)
            point["control"], point["control_correct"] = compared(
                c, stand_ins[:len(whole)], stand_ins[len(whole):], outputs)
        print(json.dumps(point), flush=True)
        if args.out:
            with open(os.path.join(ROOT, args.out), "a") as f:
                f.write(json.dumps(point) + "\n")
    c.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
