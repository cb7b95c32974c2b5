#!/usr/bin/env python
"""Serving-tier benchmark (bench.py protocol: one JSON line for the
driver; numbers recorded in docs/benchmarks.md).

Measures the serving hot path end to end on one replica — HTTP
decode excluded, batcher + compiled dispatch included — under closed-
loop concurrent load, the way an SLO is experienced:

* ``throughput_rps`` — completed predicts per second;
* ``p50_ms`` / ``p99_ms`` — per-request latency (submit → result),
  measured client-side per request (exact, not bucket-estimated);
* ``batch_mean`` — average real requests per dispatched device batch
  (how much coalescing the load actually got);
* ``cache_misses`` — compiled-program builds during the timed phase
  (MUST be 0: warm-up covers every bucket).

The model is a deliberately small MLP so the numbers characterize the
serving machinery, not the model: batcher overhead, padding waste and
program-cache dispatch are what this file guards.

``--continuous`` switches to the autoregressive closed-loop mode
(docs/serving.md "Continuous batching"): a fixed number of in-flight
streams decode through the ContinuousBatcher's slot loop, a finished
stream immediately replaced by the next arrival.  Reported:

* ``tokens_per_s`` / ``tokens_per_s_per_chip`` — generated-token
  goodput at the fixed concurrency;
* ``ttft_p50_ms`` / ``ttft_p99_ms`` — submit → first token,
  client-side per stream;
* ``tpot_p99_ms`` — p99 time per output token after the first (the
  decode-tick cadence an SLO bounds);
* ``cache_misses`` — MUST be 0: the paged-KV warmup covers every
  bucketed program.

Usage: python benchmarks/serve_bench.py [--requests N] [--concurrency C]
       python benchmarks/serve_bench.py --continuous [--streams N]
"""

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DIM, HIDDEN, OUT = 256, 512, 32


def continuous_bench(args):
    """Autoregressive closed-loop decode through the continuous
    batcher: ``--concurrency`` streams stay in flight until
    ``--streams`` sequences complete."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from horovod_tpu import telemetry
    from horovod_tpu.models.transformer import (
        TransformerConfig, TransformerLM,
    )
    from horovod_tpu.serving.continuous import ContinuousBatcher
    from horovod_tpu.serving.kvcache import PagedKVPrograms

    cfg = TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, d_ff=128, max_seq_len=128, dtype=jnp.float32)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    progs = PagedKVPrograms(cfg, max_slots=args.concurrency,
                            block_tokens=16, n_blocks=256)
    progs.warmup(params)
    miss0 = telemetry.counter_total(
        "horovod_program_cache_misses_total")

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size,
                            int(args.prompt_tokens)).tolist()
               for _ in range(args.streams)]
    bat = ContinuousBatcher(params, progs,
                            max_new_tokens=args.new_tokens)
    bat.start()

    lock = threading.Lock()
    ttfts, tpots = [], []
    done = threading.Semaphore(0)
    inflight = threading.Semaphore(args.concurrency)

    def submit(prompt):
        state = {"t0": time.perf_counter(), "last": None}

        def on_token(tok):
            now = time.perf_counter()
            if tok is None:
                inflight.release()
                done.release()
                return
            with lock:
                if state["last"] is None:
                    ttfts.append(now - state["t0"])
                else:
                    tpots.append(now - state["last"])
            state["last"] = now

        bat.submit(prompt, on_token=on_token)

    t0 = time.perf_counter()
    for prompt in prompts:
        inflight.acquire()      # closed loop: C streams in flight
        submit(prompt)
    for _ in prompts:
        done.acquire()
    wall = time.perf_counter() - t0
    bat.stop()

    n_tokens = args.streams * args.new_tokens
    chips = max(jax.local_device_count(), 1)
    ttft_ms = np.sort(np.array(ttfts)) * 1000.0
    tpot_ms = np.sort(np.array(tpots)) * 1000.0
    result = {
        "benchmark": "serve_bench_continuous",
        "streams": args.streams,
        "concurrency": args.concurrency,
        "prompt_tokens": args.prompt_tokens,
        "new_tokens": args.new_tokens,
        "model": (f"transformer L{cfg.n_layers} d{cfg.d_model} "
                  f"h{cfg.n_heads}/kv{cfg.kv_heads} f32"),
        "tokens_per_s": round(n_tokens / wall, 1),
        "tokens_per_s_per_chip": round(n_tokens / wall / chips, 1),
        "ttft_p50_ms": round(float(ttft_ms[len(ttft_ms) // 2]), 3),
        "ttft_p99_ms": round(
            float(ttft_ms[int(len(ttft_ms) * 0.99)]), 3),
        "tpot_p99_ms": round(
            float(tpot_ms[int(len(tpot_ms) * 0.99)]), 3),
        "cache_misses": telemetry.counter_total(
            "horovod_program_cache_misses_total") - miss0,
    }
    print(json.dumps(result))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=2000)
    ap.add_argument("--concurrency", type=int, default=16)
    ap.add_argument("--max-batch-size", type=int, default=16)
    ap.add_argument("--max-latency-ms", type=float, default=2.0)
    ap.add_argument("--continuous", action="store_true",
                    help="autoregressive closed-loop decode mode")
    ap.add_argument("--streams", type=int, default=64,
                    help="(--continuous) total sequences")
    ap.add_argument("--prompt-tokens", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    args = ap.parse_args()

    if args.continuous:
        if args.concurrency == 16:
            args.concurrency = 8      # decode slots, not HTTP threads
        return continuous_bench(args)

    import numpy as np

    from horovod_tpu import serving, telemetry

    rng = np.random.default_rng(0)
    params = {
        "w1": rng.standard_normal((DIM, HIDDEN)).astype(np.float32)
        / np.sqrt(DIM),
        "w2": rng.standard_normal((HIDDEN, OUT)).astype(np.float32)
        / np.sqrt(HIDDEN),
    }

    def predict_fn(p, batch):
        import jax.numpy as jnp
        h = jnp.maximum(batch["x"] @ p["w1"], 0.0)
        return {"y": h @ p["w2"]}

    replica = serving.ServingReplica(
        predict_fn, params=params,
        config=serving.ServingConfig(
            max_batch_size=args.max_batch_size,
            max_latency_ms=args.max_latency_ms))
    replica.warmup({"x": np.zeros(DIM, np.float32)})
    miss0 = telemetry.counter_total(
        "horovod_program_cache_misses_total")

    x = rng.standard_normal(DIM).astype(np.float32)
    latencies = []
    lat_lock = threading.Lock()
    idx = iter(range(args.requests))
    idx_lock = threading.Lock()

    def pump():
        local = []
        while True:
            with idx_lock:
                i = next(idx, None)
            if i is None:
                break
            t0 = time.perf_counter()
            out = replica.predict_one({"x": x})
            local.append(time.perf_counter() - t0)
            assert out["y"].shape == (OUT,)
        with lat_lock:
            latencies.extend(local)

    threads = [threading.Thread(target=pump)
               for _ in range(args.concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    lat_ms = np.sort(np.array(latencies)) * 1000.0
    occ = telemetry.registry().get("horovod_serving_batch_occupancy")
    batches = occ.total()
    result = {
        "benchmark": "serve_bench",
        "requests": args.requests,
        "concurrency": args.concurrency,
        "max_batch_size": args.max_batch_size,
        "max_latency_ms": args.max_latency_ms,
        "model": f"mlp {DIM}x{HIDDEN}x{OUT} f32",
        "throughput_rps": round(args.requests / wall, 1),
        "p50_ms": round(float(lat_ms[len(lat_ms) // 2]), 3),
        "p99_ms": round(float(lat_ms[int(len(lat_ms) * 0.99)]), 3),
        "batch_mean": round(args.requests / max(batches, 1), 2),
        "cache_misses": telemetry.counter_total(
            "horovod_program_cache_misses_total") - miss0,
    }
    replica.close()
    print(json.dumps(result))


if __name__ == "__main__":
    from horovod_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    main()
