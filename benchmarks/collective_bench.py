#!/usr/bin/env python
"""Eager collective micro-benchmark: allreduce goodput through the
full engine path (submit -> negotiate -> fuse -> native pack ->
compiled XLA collective -> unpack).

This is the engine-side analogue of the reference's fusion argument
(SURVEY §2.1 FusionBufferManager, §6): many small tensors submitted
concurrently must approach the goodput of one large tensor.  Run
single-rank on the real chip (measures staging + launch overhead —
communication is identity) or multi-rank on the virtual CPU mesh.

    python benchmarks/collective_bench.py                # 1 rank, chip
    python benchmarks/collective_bench.py --np 4 --cpu   # 4 ranks, CPU
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def worker(sizes_mb, small_count, iters):
    import numpy as np
    import horovod_tpu as hvd

    out = {}
    # one large tensor per size: bytes/sec through the whole path
    for mb in sizes_mb:
        n = int(mb * (1 << 20) / 4)
        x = np.ones(n, np.float32)
        hvd.allreduce(x, op=hvd.Sum, name=f"warm{mb}")
        t0 = time.perf_counter()
        for i in range(iters):
            hvd.allreduce(x, op=hvd.Sum, name=f"big{mb}.{i % 2}")
        dt = time.perf_counter() - t0
        out[f"allreduce_{mb}mb_MBps"] = round(
            mb * iters / dt, 1)

    # many small tensors submitted async then synchronized — the
    # fusion path (DistributedOptimizer's shape of traffic)
    small = [np.ones(64 * 1024 // 4, np.float32)  # 64 KiB each
             for _ in range(small_count)]
    handles = [hvd.allreduce_async(t, op=hvd.Sum, name=f"w.{j}")
               for j, t in enumerate(small)]
    for h in handles:
        hvd.synchronize(h)
    t0 = time.perf_counter()
    for i in range(iters):
        handles = [hvd.allreduce_async(t, op=hvd.Sum,
                                       name=f"s.{i % 2}.{j}")
                   for j, t in enumerate(small)]
        for h in handles:
            hvd.synchronize(h)
    dt = time.perf_counter() - t0
    total_mb = small_count * 64 / 1024 * iters
    out["fused_small_64k_MBps"] = round(total_mb / dt, 1)
    out["small_count"] = small_count

    # the same small-tensor group through the COMPILED (in-graph)
    # path: one cached XLA program per call, no negotiation —
    # reference xla_mpi_ops.cc role (ops/compiled.py).  force_program
    # keeps the measurement honest at world size 1 (the production
    # shortcut would otherwise reduce on the host).
    red = hvd.CompiledGroupedAllreduce(op=hvd.Sum, name="bench",
                                       force_program=True)
    red(small)                                          # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        red(small)
    dt = time.perf_counter() - t0
    out["compiled_small_64k_MBps"] = round(total_mb / dt, 1)

    # and one large buffer through the compiled path
    for mb in sizes_mb:
        n = int(mb * (1 << 20) / 4)
        x = np.ones(n, np.float32)
        red([x])
        t0 = time.perf_counter()
        for _ in range(iters):
            red([x])
        dt = time.perf_counter() - t0
        out[f"compiled_{mb}mb_MBps"] = round(mb * iters / dt, 1)
    return out


def wire_sweep(iters, wire_dtype="all", mb=8):
    """Quantized-wire section: the same logical payload through every
    wire format, on BOTH reduction paths.  Reports per dtype:

    * ``*_MBps`` — logical goodput (gradient MB averaged per second;
      the autotuner's score, core/autotune.py);
    * ``*_wire_bytes`` — what the encoding actually puts on the
      interconnect per rank (int8 = codes + one bf16 scale per
      256-element block, ~3.97x under f32);
    * ``wire_reduction_vs_f32`` — the featured dtype's byte ratio.

    All three dtypes always run (the reduction ratio needs the f32
    baseline); ``--wire-dtype`` picks which one the summary keys
    feature."""
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu import telemetry

    # wire accounting comes from registry snapshots
    # (horovod_wire_*_bytes_total families, docs/observability.md) —
    # the engine attributes those counters replaced are deprecated
    # aliases over the same families
    actual = lambda: telemetry.counter_total(  # noqa: E731
        "horovod_wire_actual_bytes_total")
    logical = lambda: telemetry.counter_total(  # noqa: E731
        "horovod_wire_logical_bytes_total")

    out = {}
    n = int(mb * (1 << 20) / 4)
    rng = np.random.default_rng(0)
    x = rng.standard_normal(n).astype(np.float32)
    for wire in (None, "bf16", "int8", "int4"):
        name = wire or "f32"
        hvd.allreduce(x, op=hvd.Sum, name=f"wire.w.{name}",
                      wire_dtype=wire)
        a0, l0 = actual(), logical()
        t0 = time.perf_counter()
        for i in range(iters):
            hvd.allreduce(x, op=hvd.Sum, name=f"wire.{name}.{i % 2}",
                          wire_dtype=wire)
        dt = time.perf_counter() - t0
        out[f"wire_{name}_engine_MBps"] = round(mb * iters / dt, 1)
        out[f"wire_{name}_engine_wire_bytes"] = \
            int(actual() - a0) // iters
        out[f"wire_{name}_logical_bytes"] = \
            int(logical() - l0) // iters

        red = hvd.CompiledGroupedAllreduce(
            op=hvd.Sum, name=f"wire.c.{name}", force_program=True,
            wire_dtype=wire)
        red([x])
        t0 = time.perf_counter()
        for _ in range(iters):
            red([x])
        dt = time.perf_counter() - t0
        out[f"wire_{name}_compiled_MBps"] = round(mb * iters / dt, 1)
        out[f"wire_{name}_compiled_wire_bytes"] = red.last_wire_bytes

    featured = "int8" if wire_dtype == "all" else wire_dtype
    out["wire_dtype"] = featured
    out["wire_reduction_vs_f32"] = round(
        out["wire_f32_engine_wire_bytes"]
        / out[f"wire_{featured}_engine_wire_bytes"], 2)
    return out


def wire_pair_sweep(iters, pair_spec="all", mb=8):
    """Per-hop wire pair section (ISSUE 9): the same logical payload
    through (inner, outer) wire pairs on the DECOMPOSED (torus)
    engine and compiled paths, against the flat paths they replace —
    including the STAGED int8 path (PR 1: host-side numpy encode ->
    all_gather-of-codes program -> host decode), which the fused
    per-hop path must beat on the 8 MiB cross-host bucket.

    Single-host runs get the simulated 2-host slot map (the
    launcher's HOROVOD_TPU_HOST_OF_RANK handoff, patched in-process)
    so the cross (DCN) hop is real.  Reports per pair:

    * ``pair_<inner>_<outer>_{engine,compiled}_MBps`` — logical
      goodput (the autotuner's score);
    * ``pair_<inner>_<outer>_inner_bytes`` / ``_cross_bytes`` — what
      the per-hop accounting (horovod_wire_hop_bytes_total) says each
      hop moved per call;

    and the headline ratios: ``fused_per_hop_vs_staged_int8`` (best
    per-hop pair over the flat staged-int8 goodput) and
    ``per_hop_vs_flat_f32`` (torus vs flat).  Only the byte counts are
    gated and documented (tools/perf_gate.py, docs/benchmarks.md);
    the MB/s here measure the CPU backend."""
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu import telemetry
    from horovod_tpu.common import basics
    from horovod_tpu.common.topology import Topology
    from horovod_tpu.ops.quantize import (WIRE_PAIR_CHOICES,
                                          normalize_wire_pair,
                                          wire_pair_label)

    eng = basics.engine()
    n_ranks = hvd.size()
    if eng.topology.num_hosts == 1 and n_ranks >= 4 \
            and n_ranks % 2 == 0:
        eng.topology = Topology(
            size=n_ranks,
            host_of_rank=[0] * (n_ranks // 2) + [1] * (n_ranks // 2))

    def hop_bytes():
        snap = telemetry.metrics().get(
            telemetry.WIRE_HOP_BYTES_FAMILY, {})
        out = {"inner": 0.0, "cross": 0.0}
        for s in snap.get("samples", []):
            hop = s.get("labels", {}).get("hop")
            if hop in out:
                out[hop] += s.get("value", 0.0)
        return out

    if pair_spec == "all":
        # the quantized-DCN slice of the legal enumeration plus the
        # full-width reference — the pairs whose cross-hop budgets
        # docs/benchmarks.md tabulates (uniform 16-bit pairs are the
        # --wire-dtype sweep's territory)
        pairs = [p for p in WIRE_PAIR_CHOICES
                 if p == (None, None) or p[1] in ("int8", "int4")]
    else:
        pairs = [normalize_wire_pair(*pair_spec.split(":"))]

    out = {}
    n = int(mb * (1 << 20) / 4)
    rng = np.random.default_rng(0)
    x = rng.standard_normal(n).astype(np.float32)

    def time_engine(tag, **kw):
        hvd.allreduce(x, op=hvd.Sum, name=f"{tag}.w", **kw)
        h0 = hop_bytes()
        t0 = time.perf_counter()
        for i in range(iters):
            hvd.allreduce(x, op=hvd.Sum, name=f"{tag}.{i % 2}", **kw)
        dt = time.perf_counter() - t0
        h1 = hop_bytes()
        return (round(mb * iters / dt, 1),
                int(h1["inner"] - h0["inner"]) // iters,
                int(h1["cross"] - h0["cross"]) // iters)

    # the flat baselines this PR's fused path is judged against:
    # full-width flat, and PR 1's staged int8 (host codec + separate
    # quantized program)
    out["flat_f32_engine_MBps"], _, _ = time_engine("wp.flatf32")
    out["staged_int8_engine_MBps"], _, _ = time_engine(
        "wp.staged8", wire_dtype="int8")

    for inner, outer in pairs:
        label = wire_pair_label(inner, outer).replace(":", "_")
        tag = f"pair_{label}"
        mbps, ib, cb = time_engine(
            f"wp.{label}", algorithm="torus",
            wire_dtype=outer or "f32", wire_inner=inner or "f32")
        out[f"{tag}_engine_MBps"] = mbps
        out[f"{tag}_inner_bytes"] = ib
        out[f"{tag}_cross_bytes"] = cb

        red = hvd.CompiledGroupedAllreduce(
            op=hvd.Sum, name=f"wp.c.{label}", force_program=True,
            algorithm="torus", wire_dtype=outer, wire_inner=inner)
        red([x])
        t0 = time.perf_counter()
        for _ in range(iters):
            red([x])
        dt = time.perf_counter() - t0
        out[f"{tag}_compiled_MBps"] = round(mb * iters / dt, 1)
        out[f"{tag}_compiled_cross_bytes"] = red.last_cross_bytes

    quant = [(i, o) for i, o in pairs if o in ("int8", "int4")]
    if quant:
        best_pair = max(quant, key=lambda p: out[
            f"pair_{wire_pair_label(*p).replace(':', '_')}"
            "_engine_MBps"])
        best_key = f"pair_{wire_pair_label(*best_pair).replace(':', '_')}"
        out["per_hop_best_pair"] = wire_pair_label(*best_pair)
        out["fused_per_hop_vs_staged_int8"] = round(
            out[f"{best_key}_engine_MBps"]
            / out["staged_int8_engine_MBps"], 2)
        out["per_hop_vs_flat_f32"] = round(
            out[f"{best_key}_engine_MBps"]
            / out["flat_f32_engine_MBps"], 2)
    return out


def algo_sweep(iters, algorithm="all", sizes_mb=(1, 8, 32)):
    """Topology-aware section (ISSUE 2): the same logical payload
    through flat / hierarchical / torus on BOTH reduction paths.
    Reports per (algorithm, size):

    * ``*_MBps`` — logical goodput through the engine / compiled path;
    * ``*_cross_bytes`` — what the engine's accounting says crossed
      the slow (cross-host / DCN) hop per call: flat pays its whole
      wire there, hierarchical/torus only 1/local_size of it.

    Single-host jobs get a simulated 2-host slot map (the launcher's
    HOROVOD_TPU_HOST_OF_RANK handoff, patched in-process) so the
    hierarchical split is real; launched multi-host jobs use their
    true topology.  A short engine-autotune session (six-dimension BO,
    core/autotune.py) runs at the end and the converged algorithm is
    recorded as ``autotune_algorithm_pick``."""
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu import telemetry
    from horovod_tpu.common import basics
    from horovod_tpu.common.topology import Topology

    cross = lambda: telemetry.counter_total(  # noqa: E731
        "horovod_wire_cross_bytes_total")
    eng = basics.engine()
    n_ranks = hvd.size()
    if eng.topology.num_hosts == 1 and n_ranks >= 4 \
            and n_ranks % 2 == 0:
        # equivalent assignment from every rank thread — idempotent
        eng.topology = Topology(
            size=n_ranks,
            host_of_rank=[0] * (n_ranks // 2) + [1] * (n_ranks // 2))

    algos = ("flat", "hierarchical", "torus") \
        if algorithm == "all" else (algorithm,)
    out = {}
    rng = np.random.default_rng(0)
    for mb in sizes_mb:
        n = int(mb * (1 << 20) / 4)
        x = rng.standard_normal(n).astype(np.float32)
        for algo in algos:
            tag = f"algo_{algo}_{mb}mb"
            hvd.allreduce(x, op=hvd.Sum, name=f"{tag}.w",
                          algorithm=algo)
            c0 = cross()
            t0 = time.perf_counter()
            for i in range(iters):
                hvd.allreduce(x, op=hvd.Sum, name=f"{tag}.{i % 2}",
                              algorithm=algo)
            dt = time.perf_counter() - t0
            out[f"{tag}_engine_MBps"] = round(mb * iters / dt, 1)
            out[f"{tag}_engine_cross_bytes"] = \
                int(cross() - c0) // iters

            red = hvd.CompiledGroupedAllreduce(
                op=hvd.Sum, name=f"{tag}.c", force_program=True,
                algorithm=algo)
            red([x])
            t0 = time.perf_counter()
            for _ in range(iters):
                red([x])
            dt = time.perf_counter() - t0
            out[f"{tag}_compiled_MBps"] = round(mb * iters / dt, 1)
            out[f"{tag}_compiled_cross_bytes"] = red.last_cross_bytes
            out[f"{tag}_resolved"] = red.last_algorithm

    # short real-traffic autotune session: does the six-dimension BO
    # (fusion/cycle/pack/cache/wire/algorithm) land on a non-flat
    # algorithm for this configuration?
    from horovod_tpu.core.autotune import ParameterManager
    old_wire, old_algo = eng.config.wire_dtype, eng.config.algorithm
    old_inner = eng.config.wire_inner
    pm = None
    if hvd.rank() == 0:
        pm = ParameterManager(eng.config, warmup_samples=2,
                              steps_per_sample=4, max_samples=14)
        eng.autotuner = pm
    xat = rng.standard_normal(int(4 * (1 << 20) / 4)) \
        .astype(np.float32)
    for i in range(15 * 4 + 4):
        hvd.allreduce(xat, op=hvd.Sum, name=f"algo_at.{i % 2}")
    if pm is not None:
        from horovod_tpu.ops.quantize import wire_pair_label
        eng.autotuner = None
        best = pm.best_parameters()
        out["autotune_algorithm_pick"] = best[5]
        out["autotune_wire_pick"] = wire_pair_label(*best[4])
        pm.close()
        eng.config.wire_dtype, eng.config.algorithm = old_wire, old_algo
        eng.config.wire_inner = old_inner
    return out


def proc_worker(small_count, iters):
    """Runs inside one launcher-spawned process: the store-controller
    (coordinator) negotiation path the thread launcher bypasses."""
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    r = hvd.rank()
    out = {"np": hvd.size()}

    # steady-state negotiated cycle latency: one small sequential op
    # per cycle.  The reference's claim is a cached cycle costs two
    # bitvector allreduces (response_cache.h:107-169); here it is one
    # ready-report POST + one long-poll wake per process.
    x = np.ones(1024, np.float32)
    for i in range(6):
        hvd.allreduce(x, op=hvd.Sum, name=f"lat.w{i % 2}")
    t0 = time.perf_counter()
    lat_iters = 40
    for i in range(lat_iters):
        hvd.allreduce(x, op=hvd.Sum, name=f"lat.{i % 2}")
    out["eager_cycle_latency_ms"] = round(
        (time.perf_counter() - t0) / lat_iters * 1e3, 2)

    # eager fused allreduce goodput: 64 KiB x small_count burst
    small = [np.ones(64 * 1024 // 4, np.float32)
             for _ in range(small_count)]
    for i in range(2):
        hs = [hvd.allreduce_async(t, op=hvd.Sum, name=f"w.{i}.{j}")
              for j, t in enumerate(small)]
        [hvd.synchronize(h) for h in hs]
    t0 = time.perf_counter()
    for i in range(iters):
        hs = [hvd.allreduce_async(t, op=hvd.Sum, name=f"s.{i % 2}.{j}")
              for j, t in enumerate(small)]
        [hvd.synchronize(h) for h in hs]
    dt = time.perf_counter() - t0
    total_mb = small_count * 64 / 1024 * iters
    out["fused_small_64k_MBps"] = round(total_mb / dt, 1)

    # allgather: fused burst of small tensors vs ONE equal-bytes
    # gather (VERDICT r5 item 5 'fused ~ single-large for allgather')
    rows = 64 * 1024 // 8
    ag_small = [np.ones((rows, 2), np.float32)
                for _ in range(small_count)]
    for i in range(2):
        hs = [hvd.allgather_async(t, name=f"agw.{i}.{j}")
              for j, t in enumerate(ag_small)]
        [hvd.synchronize(h) for h in hs]
    t0 = time.perf_counter()
    for i in range(iters):
        hs = [hvd.allgather_async(t, name=f"ag.{i % 2}.{j}")
              for j, t in enumerate(ag_small)]
        [hvd.synchronize(h) for h in hs]
    dt = time.perf_counter() - t0
    out["allgather_fused_small_MBps"] = round(total_mb / dt, 1)

    big = np.ones((rows * small_count, 2), np.float32)
    for i in range(2):
        hvd.allgather(big, name=f"agbw.{i}")
    t0 = time.perf_counter()
    for i in range(iters):
        hvd.allgather(big, name=f"agb.{i % 2}")
    dt = time.perf_counter() - t0
    out["allgather_single_large_MBps"] = round(total_mb / dt, 1)

    from horovod_tpu import telemetry
    out["fused_allgather_runs"] = int(telemetry.counter_total(
        "horovod_fused_allgather_runs_total"))
    # steady-state negotiation latency straight from the histogram the
    # engine exports (mean over the run; the /metrics scrape carries
    # the full distribution)
    neg = telemetry.metrics().get("horovod_negotiation_seconds", {})
    n = sum(s.get("count", 0) for s in neg.get("samples", []))
    tot = sum(s.get("sum", 0.0) for s in neg.get("samples", []))
    if n:
        out["negotiation_mean_ms"] = round(tot / n * 1e3, 3)
    if r == 0:
        dest = os.environ.get("CB_OUT")
        payload = json.dumps(out)
        if dest:
            with open(dest, "w") as f:
                f.write(payload)
        print(payload)
    hvd.shutdown()


def bypass_worker():
    """Runs inside one launcher-spawned process: steady-state
    negotiated cycle latency with ONE repeated tensor name — the
    training-loop shape the bypass (core/bypass.py, ROADMAP item 2)
    fast-paths.  With HOROVOD_BYPASS_AFTER_CYCLES set the cycle
    becomes a 1-element agreement allreduce + the payload program;
    with it 0 every cycle pays the ready-POST + long-poll round trip
    against the coordinator."""
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu import telemetry

    hvd.init()
    x = np.ones(1024, np.float32)
    for _ in range(10):                      # warm-up + arming window
        hvd.allreduce(x, op=hvd.Sum, name="bp.lat")
    iters = int(os.environ.get("CB_ITERS", "200"))
    t0 = time.perf_counter()
    for _ in range(iters):
        hvd.allreduce(x, op=hvd.Sum, name="bp.lat")
    dt = time.perf_counter() - t0
    out = {
        "cycle_latency_ms": round(dt / iters * 1e3, 3),
        "bypass_hits": telemetry.counter_total(
            "horovod_negotiation_bypass_cycles_total", outcome="hit"),
    }
    if hvd.rank() == 0:
        dest = os.environ.get("CB_OUT")
        if dest:
            with open(dest, "w") as f:
                f.write(json.dumps(out))
        print(json.dumps(out))
    hvd.barrier()
    hvd.shutdown()


def run_bypass_compare(np_, iters):
    """Spawn the REAL launcher twice — bypass armed (K=3) vs disabled
    — and report the steady-state cycle-latency ratio."""
    import tempfile

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from horovod_tpu.runner.proc_run import launch_procs

    results = {}
    for label, k in (("bypass", "3"), ("full_poll", "0")):
        with tempfile.TemporaryDirectory() as td:
            dest = os.path.join(td, "out.json")
            env = {"PYTHONPATH": repo, "CB_OUT": dest,
                   "CB_BYPASS_WORKER": "1", "CB_ITERS": str(iters),
                   "HOROVOD_BYPASS_AFTER_CYCLES": k}
            codes = launch_procs(
                [sys.executable, os.path.abspath(__file__)], np=np_,
                platform="cpu", env=env, start_timeout=300)
            if any(codes):
                results[label] = {"error": f"exit {codes}"}
                continue
            with open(dest) as f:
                results[label] = json.load(f)
    try:
        results["bypass_speedup"] = round(
            results["full_poll"]["cycle_latency_ms"]
            / results["bypass"]["cycle_latency_ms"], 2)
    except (KeyError, ZeroDivisionError):
        pass
    print(json.dumps(results))
    return results


def run_proc_curve(np_list, small_count, iters):
    """Spawn the real launcher at each process count and collect the
    coordinator-path numbers (VERDICT r5 item 3: negotiation-overhead
    scaling curve)."""
    import tempfile

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from horovod_tpu.runner.proc_run import launch_procs

    results = []
    for n in np_list:
        with tempfile.TemporaryDirectory() as td:
            dest = os.path.join(td, "out.json")
            env = {"PYTHONPATH": repo, "CB_OUT": dest,
                   "CB_WORKER": "1",
                   "CB_SMALL_COUNT": str(small_count),
                   "CB_ITERS": str(iters)}
            codes = launch_procs(
                [sys.executable, os.path.abspath(__file__)], np=n,
                platform="cpu", env=env, start_timeout=300)
            if any(codes):
                results.append({"np": n, "error": f"exit {codes}"})
                continue
            with open(dest) as f:
                results.append(json.load(f))
    for row in results:
        print(json.dumps(row))
    return results


def main():
    if os.environ.get("CB_BYPASS_WORKER"):
        bypass_worker()
        return
    if os.environ.get("CB_WORKER"):
        proc_worker(int(os.environ.get("CB_SMALL_COUNT", "64")),
                    int(os.environ.get("CB_ITERS", "5")))
        return

    p = argparse.ArgumentParser()
    p.add_argument("--np", type=int, default=1)
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--sizes-mb", default="1,16,64")
    p.add_argument("--small-count", type=int, default=64)
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--wire-dtype", default=None,
                   choices=["f32", "bf16", "int8", "int4", "all"],
                   help="run the quantized-wire sweep (engine + "
                        "compiled paths, every dtype measured; the "
                        "chosen dtype is featured in "
                        "wire_reduction_vs_f32).  As a per-call knob "
                        "this remains the UNIFORM shorthand for a "
                        "per-hop pair (--wire-pair)")
    p.add_argument("--wire-pair", default=None,
                   help="run the per-hop pair sweep: 'inner:outer' "
                        "(e.g. bf16:int4) or 'all' — decomposed "
                        "torus engine+compiled paths vs the flat "
                        "staged-int8 baseline, with per-hop byte "
                        "accounting (docs/benchmarks.md)")
    p.add_argument("--algorithm", default=None,
                   choices=["flat", "hier", "hierarchical", "torus",
                            "all"],
                   help="run the topology-aware sweep: the same "
                        "payload through flat / hierarchical / torus "
                        "on both paths, with cross-host byte "
                        "accounting and a six-dimension autotune "
                        "session at the end")
    p.add_argument("--proc-curve", default=None,
                   help="comma list of process counts, e.g. 1,2,4,8: "
                        "run the REAL launcher + coordinator at each "
                        "and print one JSON row per count")
    p.add_argument("--bypass-compare", action="store_true",
                   help="steady-state cycle latency with the "
                        "negotiation bypass armed vs the full "
                        "ready/poll path, on a REAL --np-process job")
    args = p.parse_args()

    if args.bypass_compare:
        run_bypass_compare(max(args.np, 2),
                           max(args.iters, 50) if args.iters != 5
                           else 200)
        return

    if args.proc_curve:
        run_proc_curve([int(x) for x in args.proc_curve.split(",")],
                       args.small_count, args.iters)
        return

    if args.cpu:
        os.environ["HOROVOD_TPU_PLATFORM"] = "cpu"
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import jax
        jax.config.update("jax_num_cpu_devices", max(args.np, 2))

    import horovod_tpu as hvd

    sizes = [int(s) for s in args.sizes_mb.split(",")]

    def body():
        if args.algorithm:
            algo = "hierarchical" if args.algorithm == "hier" \
                else args.algorithm
            return algo_sweep(args.iters, algo, tuple(sizes))
        if args.wire_pair:
            return wire_pair_sweep(args.iters, args.wire_pair)
        if args.wire_dtype:
            return wire_sweep(args.iters, args.wire_dtype)
        return worker(sizes, args.small_count, args.iters)

    if args.np == 1:
        hvd.init(num_ranks=1)
        res = body()
    else:
        res = hvd.run(body, np=args.np)[0]
    res["np"] = args.np
    print(json.dumps(res))


if __name__ == "__main__":
    from horovod_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    main()
