#!/usr/bin/env python
"""Async-checkpoint step-time impact micro-bench (ci.sh ``perf``).

The async CRC-anchored checkpointer's whole claim is that saves leave
the step path (docs/data.md "Async checkpointing"): the rank streams
its CRC-trailed shard from a background thread while training keeps
stepping.  This bench measures that claim as a number the perf gate
can hold:

* ``plain``  — the synthetic train step alone (fixed CPU work);
* ``async``  — the same step + ``AsyncCheckpointer.save`` per step
  (background thread, the shipped default);
* ``sync``   — the same step with ``wait=True`` (the blocking cost
  the async path is supposed to hide).

Emits one JSON row (last line) with the per-mode step times, the
async overhead fraction vs plain — the gated step-time impact — and
the anchored fraction (every async commit must still land; hiding
the write must never mean losing it).
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from horovod_tpu.utils.checkpoint import AsyncCheckpointer  # noqa: E402


def _state(mb):
    rng = np.random.default_rng(20260807)
    n = int(mb * (1 << 20) // 8 // 4)
    return {f"w{i}": rng.standard_normal(n) for i in range(4)}


def run_mode(mode, steps, work_iters, state, every):
    # fat matmuls release the GIL — the synthetic step behaves like a
    # real host feeding a device, so background pickling can overlap
    a = np.random.default_rng(0).standard_normal((512, 512))
    tmp = tempfile.mkdtemp(prefix=f"ckpt_bench_{mode}_")
    ckpt = None if mode == "plain" else AsyncCheckpointer(
        tmp, rank=0, world=1, commit_timeout=30.0)
    saves = 0
    t0 = time.perf_counter()
    for s in range(steps):
        for _ in range(work_iters):
            a = np.tanh(a @ a * 1e-3)
        if ckpt is not None and s % every == 0:
            ckpt.save(s, state, wait=(mode == "sync"))
            saves += 1
    if ckpt is not None:
        ckpt.wait()
    dt = (time.perf_counter() - t0) / steps
    anchored = len(ckpt.anchored_steps()) if ckpt is not None else 0
    if ckpt is not None:
        ckpt.close()
    shutil.rmtree(tmp, ignore_errors=True)
    return dt, anchored, saves


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--work-iters", type=int, default=8,
                    help="matmul iterations per synthetic step")
    ap.add_argument("--state-mb", type=float, default=8.0,
                    help="checkpoint payload size")
    ap.add_argument("--ckpt-every", type=int, default=5,
                    help="save cadence in steps (the write must hide "
                         "behind this much compute)")
    args = ap.parse_args()

    state = _state(args.state_mb)
    row = {}
    anchored = {}
    saves = {}
    for mode in ("plain", "async", "sync"):
        dt, anc, n = run_mode(mode, args.steps, args.work_iters,
                              state, args.ckpt_every)
        row[f"ckpt_{mode}_step_ms"] = round(dt * 1000.0, 3)
        anchored[mode], saves[mode] = anc, n
        print(f"[ckpt_bench] {mode}: {dt * 1000.0:.2f} ms/step "
              f"({anc}/{n} anchored)", flush=True)
    row["ckpt_async_overhead_frac"] = round(
        row["ckpt_async_step_ms"] / row["ckpt_plain_step_ms"] - 1.0, 3)
    row["ckpt_sync_overhead_frac"] = round(
        row["ckpt_sync_step_ms"] / row["ckpt_plain_step_ms"] - 1.0, 3)
    row["ckpt_async_anchored_frac"] = round(
        anchored["async"] / max(saves["async"], 1), 3)
    print(json.dumps(row, sort_keys=True))


if __name__ == "__main__":
    from horovod_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    main()
