#!/usr/bin/env python
"""Skew-aware alltoall crossover experiment (VERDICT r5 item 8).

Times the engine alltoall at R ranks under three skew levels with the
schedule FORCED each way (``HOROVOD_TPU_ALLTOALL_SCHEDULE``), so the
one-shot padded layout and the diagonal ppermute schedule are compared
on identical traffic, validating (or correcting) the ">2x wire bytes"
auto-switch threshold.  Wall time includes host staging — the
diagonal path stages R separate padded buffers per rank, which is its
real cost.

    python benchmarks/alltoall_bench.py --np 8
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def patterns(R, base):
    """(name, splits_fn(rank) -> list, description)."""
    return [
        ("uniform", lambda r: [base] * R),
        # one hot destination per rank ON the same diagonal: the
        # diagonal schedule pads only that diagonal (wire ratio ~5.6)
        ("one_diag_skew_16x", lambda r: [
            base * 16 if j == (r + 1) % R else base for j in range(R)]),
        # scattered skew (odd diagonals hot): padding hits half the
        # diagonals (wire ratio ~1.9)
        ("scattered_skew_16x", lambda r: [
            base * 16 if j == (r * 3 + 1) % R else base
            for j in range(R)]),
        # hot segments on 6 of R diagonals — the near-crossover point
        # (wire ratio ~1.3) that set the auto threshold
        ("six_diag_skew_16x", lambda r: [
            base * 16 if j == (r + 1 + (r % 6)) % R else base
            for j in range(R)]),
    ]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--np", type=int, default=8)
    p.add_argument("--base", type=int, default=256,
                   help="base rows per destination")
    p.add_argument("--rest", type=int, default=64,
                   help="row width (f32 elements)")
    p.add_argument("--iters", type=int, default=8)
    args = p.parse_args()

    os.environ["HOROVOD_TPU_PLATFORM"] = "cpu"
    import jax
    jax.config.update("jax_num_cpu_devices", max(args.np, 2))

    import numpy as np
    import horovod_tpu as hvd

    R = args.np

    def worker():
        r = hvd.rank()
        rows = {}
        for name, fn in patterns(R, args.base):
            splits = fn(r)
            x = np.random.RandomState(r).rand(
                sum(splits), args.rest).astype(np.float32)
            row = {"pattern": name}
            wire = {}
            for mode in ("oneshot", "diag"):
                os.environ["HOROVOD_TPU_ALLTOALL_SCHEDULE"] = mode
                out, recv = hvd.alltoall(
                    x, splits=splits, name=f"w.{name}.{mode}")
                t0 = time.perf_counter()
                for i in range(args.iters):
                    hvd.alltoall(x, splits=splits,
                                 name=f"b.{name}.{mode}.{i % 2}")
                dt = time.perf_counter() - t0
                row[f"{mode}_ms"] = round(dt / args.iters * 1e3, 2)
            os.environ["HOROVOD_TPU_ALLTOALL_SCHEDULE"] = "auto"
            # wire-byte model behind the auto threshold
            all_splits = [fn(j) for j in range(R)]
            max_seg = max(max(s) for s in all_splits)
            diag_max = [max(all_splits[j][(j + d) % R]
                            for j in range(R)) for d in range(R)]
            row["oneshot_wire_rows"] = R * max_seg
            row["diag_wire_rows"] = sum(diag_max)
            row["wire_ratio"] = round(R * max_seg / sum(diag_max), 2)
            row["auto_picks"] = "diag" \
                if 4 * R * max_seg > 5 * sum(diag_max) else "oneshot"
            rows[name] = row
        return rows if r == 0 else None

    res = [x for x in hvd.run(worker, np=R) if x][0]
    for name, row in res.items():
        print(json.dumps(row))


if __name__ == "__main__":
    from horovod_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    main()
