#!/usr/bin/env python
"""Transformer-LM training throughput: pallas flash attention vs the
XLA dense path on one chip.

The reference has no long-context subsystem (SURVEY §5.7); this bench
records the beyond-parity numbers for ours: tokens/sec of the full
train step (fwd+bwd+adamw) at growing sequence lengths, with
``attention_impl="flash"`` (ops/pallas_kernels.py custom-VJP kernel,
O(S) memory) against the dense S^2 softmax.

    python benchmarks/lm_bench.py                 # real chip
    python benchmarks/lm_bench.py --seq 4096 --iters 10
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax


def parse_parallelism(text):
    """``--parallelism dp,tp,pp`` → (dp, tp, pp) ints (docs/
    parallelism.md; pp > 1 routes through the MPMD runtime)."""
    parts = [int(x) for x in str(text).split(",")]
    if len(parts) != 3 or any(x < 1 for x in parts):
        raise ValueError(
            f"--parallelism wants 'dp,tp,pp' positive ints, got "
            f"{text!r}")
    return tuple(parts)


def lm_param_count(vocab, d_model, layers, d_ff):
    """Analytic parameter count of the TransformerLM (tied embedding):
    embed + per-layer (qkv + proj + mlp + 2 LN) + final LN."""
    per_layer = 4 * d_model * d_model + 2 * d_model * d_ff \
        + 4 * d_model + d_ff + d_model
    return vocab * d_model + layers * per_layer + 2 * d_model


def memory_verdict(n_params, dp, budget_gb, param_bytes=2,
                   opt_bytes=8, sharded=False):
    """Estimated per-device training footprint (params + grads at the
    model dtype, adam moments f32 — ÷dp under weight-update sharding)
    against the device budget.  The skip-vs-run asymmetry this gate
    produces IS the sharding memory evidence."""
    opt = opt_bytes / (dp if sharded else 1)
    need_gb = n_params * (2 * param_bytes + opt) / 1e9
    return need_gb, need_gb <= budget_gb


def device_budget_gb(default=16.0):
    try:
        stats = jax.devices()[0].memory_stats() or {}
        limit = stats.get("bytes_limit")
        if limit:
            return limit / 1e9
    except Exception:  # noqa: BLE001 — CPU backends have no stats
        pass
    return default


def overlap_grad_shapes(d_model, layers, embed_rows=4096):
    """Transformer-gradient shapes in backward-readiness order (last
    layer first, tied embedding last — the order autograd hands them
    to the hook).  The embedding rows are capped: the harness measures
    dispatch overlap, not embedding-table bandwidth."""
    shapes = []
    for _ in range(layers):
        shapes += [(d_model, 3 * d_model), (d_model, d_model),
                   (d_model, 4 * d_model), (4 * d_model, d_model),
                   (d_model,), (d_model,)]
    shapes.append((d_model,))                # final LN
    shapes.append((embed_rows, d_model))     # embedding, ready last
    return shapes


def bench_overlap(args, dp, tp):
    """A/B the compiled path's grouped vs bucket-granular dispatch
    (``ci.sh perf`` overlap gate).

    The SPMD train step above never touches ops/compiled.py, so this
    leg drives CompiledGroupedAllreduce directly under hvd.run rank
    threads: per gradient tensor, burn a fixed slice of host compute
    (the stand-in for the next layer's backward) then push it into the
    stream.  The grouped leg's single bucket closes at the LAST push —
    all wire time lands exposed in result(); the bucketized leg's
    early buckets fly while later chunks still compute.  Same inputs,
    same compute, same wire — the delta is purely what the overlap
    hides."""
    import horovod_tpu as hvd

    shapes = overlap_grad_shapes(args.d_model, args.layers,
                                 embed_rows=args.overlap_embed_rows)
    bucket_bytes = args.overlap_bucket_bytes
    iters, warmup = args.iters, args.warmup
    compute_s = args.overlap_compute_ms / 1000.0
    hint = hvd.TopologyHint(axes=("dp", "tp"), sizes=(dp, tp)) \
        if tp > 1 else None
    # hvd.run ranks are threads in THIS process and the cache-miss
    # counter is process-global: without a barrier around each leg's
    # counted window, a fast rank entering the next leg's warmup
    # (compiling new bucket programs) races a slow rank that hasn't
    # read its end-of-window counter yet, and the miss gets blamed on
    # steady state — the overlap_steady_recompiles flake
    import threading
    bar = threading.Barrier(dp * tp)

    def worker():
        from horovod_tpu import telemetry

        reg = telemetry.registry()
        exposed = reg.counter(
            telemetry.EXPOSED_COMM_SECONDS_FAMILY,
            telemetry.EXPOSED_COMM_SECONDS_HELP,
            labelnames=telemetry.EXPOSED_COMM_SECONDS_LABELS)
        rng = np.random.default_rng(20260806 + hvd.rank())
        xs = [rng.standard_normal(s).astype(np.float32)
              for s in shapes]
        specs = [(x.shape, x.dtype) for x in xs]
        a = rng.standard_normal((96, 96)).astype(np.float32)

        def busy(seconds):
            end = time.perf_counter() + seconds
            while time.perf_counter() < end:
                np.dot(a, a)

        row, leg_outs = {}, {}
        for leg, bb in (("grouped", 0), ("bucketized", bucket_bytes)):
            red = hvd.CompiledGroupedAllreduce(
                op=hvd.Sum, name=f"lmov.{leg}", force_program=True,
                bucket_bytes=bb, topology_hint=hint)

            def step():
                st = red.stream(specs)
                for i, x in enumerate(xs):
                    busy(compute_s)
                    st.push(i, x)
                return st.result()

            for _ in range(warmup):
                outs = step()
            # one extra warm step OUTSIDE the counted window (a rank
            # that lost the dispatch race can trigger a late
            # first-use compile on the last nominal warmup step),
            # then barrier: no rank opens its window while another is
            # still warming (= still compiling)
            outs = step()
            bar.wait()
            m0 = telemetry.counter_total(
                telemetry.PROGRAM_CACHE_MISSES_FAMILY)
            e0 = exposed.labels(path=leg).value
            t0 = time.perf_counter()
            for _ in range(iters):
                outs = step()
            dt = time.perf_counter() - t0
            leg_outs[leg] = outs
            row[f"overlap_{leg}_step_ms"] = dt / iters * 1000.0
            row[f"overlap_{leg}_exposed_s"] = \
                exposed.labels(path=leg).value - e0
            # cache-miss counter is process-global: any rank seeing a
            # miss inside its timed window is a steady-state recompile
            row[f"overlap_{leg}_recompiles"] = \
                telemetry.counter_total(
                    telemetry.PROGRAM_CACHE_MISSES_FAMILY) - m0
            # barrier again: every rank reads its window-end counter
            # before any rank compiles the next leg's programs
            bar.wait()
        row["parity"] = all(
            np.array_equal(g, b) for g, b in
            zip(leg_outs["grouped"], leg_outs["bucketized"]))
        return row

    rows = hvd.run(worker, np=dp * tp)
    out = {"overlap_bucket_bytes": bucket_bytes,
           "overlap_n_tensors": len(shapes),
           "overlap_compute_ms_per_tensor": args.overlap_compute_ms}
    for leg in ("grouped", "bucketized"):
        out[f"overlap_{leg}_step_ms"] = round(float(np.mean(
            [r[f"overlap_{leg}_step_ms"] for r in rows])), 2)
        out[f"overlap_{leg}_exposed_s"] = round(float(np.mean(
            [r[f"overlap_{leg}_exposed_s"] for r in rows])), 4)
    out["overlap_exposed_reduction"] = round(
        out["overlap_grouped_exposed_s"]
        / max(out["overlap_bucketized_exposed_s"], 1e-9), 3)
    out["overlap_step_win"] = round(
        out["overlap_grouped_step_ms"]
        / max(out["overlap_bucketized_step_ms"], 1e-9), 3)
    out["overlap_steady_recompiles"] = int(max(
        r[f"overlap_{leg}_recompiles"] for r in rows
        for leg in ("grouped", "bucketized")))
    out["overlap_bitwise_parity"] = float(all(
        r["parity"] for r in rows))
    return out


def bench_moe(args):
    """Expert-parallel loss-parity gate (``ci.sh perf`` moe leg).

    Trains the capacity-routed MoE transformer and a dense baseline
    whose FFN width FLOP-matches the top-k expert compute
    (``parallel/moe.dense_flop_matched_ff``) on IDENTICAL data, then
    scrapes the quantized engine alltoall that multi-process expert
    dispatch rides.  Emits the final losses and their relative gap
    (the <=1% acceptance bar), tokens/sec for both legs, the
    steady-state recompile count of the compiled MoE step (the
    fixed-capacity dispatch keeps every shape static, so the timed
    window must never re-enter XLA), and the int8 alltoall
    logical/actual wire ratio from the telemetry counters."""
    import optax
    from jax import monitoring

    from horovod_tpu.models import TransformerConfig
    from horovod_tpu.parallel import (
        MeshSpec, build_mesh, dense_flop_matched_ff, make_lm_train_step,
    )

    compiles = [0]

    def _on_event(name, *_a, **_kw):
        if name.endswith("backend_compile_duration"):
            compiles[0] += 1

    monitoring.register_event_duration_secs_listener(_on_event)

    E, K, CF = args.moe_experts, args.moe_topk, args.moe_capacity_factor
    # per-expert hidden chosen so the top-k expert FLOPs equal the
    # dense leg's FFN: the two legs differ only in routing
    d_ff_expert = max((4 * args.d_model) // K, 8)
    legs = (
        ("moe", dict(num_experts=E, expert_top_k=K,
                     moe_capacity_factor=CF, d_ff=d_ff_expert)),
        ("dense_matched",
         dict(d_ff=dense_flop_matched_ff(d_ff_expert, K))),
    )
    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.seq), 0, 32000)
    out = {"moe_experts": E, "moe_topk": K, "moe_capacity_factor": CF,
           "moe_d_ff_expert": d_ff_expert,
           "dense_matched_d_ff": dense_flop_matched_ff(d_ff_expert, K)}
    for leg, kw in legs:
        cfg = TransformerConfig(
            vocab_size=32000, d_model=args.d_model,
            n_layers=args.layers, n_heads=args.heads,
            max_seq_len=args.seq, dtype=jnp.bfloat16,
            remat=args.remat, **kw)
        init, _, jit_step, tok_shd = make_lm_train_step(
            mesh, cfg, optimizer=optax.adamw(1e-3))
        state = init(jax.random.PRNGKey(0), tokens)
        compiled, state = jit_step(state)
        toks = jax.device_put(tokens, tok_shd)
        for _ in range(args.warmup):
            state, loss = compiled(state, toks)
        float(loss)                       # drain warmup compiles
        c0 = compiles[0]
        t0 = time.perf_counter()
        for _ in range(args.iters):
            state, loss = compiled(state, toks)
        lv = float(loss)
        dt = time.perf_counter() - t0
        out[f"{leg}_loss"] = round(lv, 4)
        out[f"{leg}_tokens_per_sec"] = round(
            tokens.size * args.iters / dt, 1)
        if leg == "moe":
            out["moe_steady_recompiles"] = compiles[0] - c0
    out["moe_loss_gap"] = round(
        abs(out["moe_loss"] - out["dense_matched_loss"])
        / max(out["dense_matched_loss"], 1e-9), 4)
    out.update(_moe_alltoall_scrape())
    return out


def _moe_alltoall_scrape():
    """4-rank engine job pushing the MoE dispatch wire: quantized
    int8 alltoalls, ratio read back from the
    ``horovod_alltoall_*_bytes_total`` counters — the telemetry the
    wire-reduction acceptance bar is scraped from."""
    import horovod_tpu as hvd

    def worker():
        from horovod_tpu import telemetry

        R = hvd.size()
        rng = np.random.default_rng(20260806 + hvd.rank())
        x = rng.standard_normal((R * 2048,)).astype(np.float32)
        for _ in range(4):
            hvd.alltoall(x, wire_dtype="int8", name="moe.dispatch")
        if hvd.rank() != 0:
            return None
        lg = telemetry.counter_total(
            telemetry.ALLTOALL_LOGICAL_BYTES_FAMILY)
        ac = telemetry.counter_total(
            telemetry.ALLTOALL_WIRE_BYTES_FAMILY)
        return lg / max(ac, 1e-9)

    rows = hvd.run(worker, np=4)
    ratio = next(r for r in rows if r)
    return {"moe_alltoall_int8_ratio": round(float(ratio), 3)}


def bench_impl(impl, cfg, tokens, mesh, iters, warmup, pipeline=None,
               sharded=False):
    from horovod_tpu.parallel import make_lm_train_step

    init, _, jit_step, tok_shd = make_lm_train_step(
        mesh, cfg, optimizer=optax.adamw(1e-3), attention_impl=impl,
        pipeline=pipeline, sharded=sharded)
    if iters < 1 or warmup < 1:
        raise ValueError("--iters and --warmup must be >= 1")
    state = init(jax.random.PRNGKey(0), tokens)
    compiled, state = jit_step(state)
    toks = jax.device_put(tokens, tok_shd)
    for _ in range(warmup):
        state, loss = compiled(state, toks)
    float(loss)   # value-forcing sync: waits for the whole chain
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = compiled(state, toks)
    lv = float(loss)
    dt = time.perf_counter() - t0
    return tokens.size * iters / dt, lv


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--d-model", type=int, default=512)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--impls", default="flash,dense")
    p.add_argument("--remat", action="store_true",
                   help="jax.checkpoint each block (required for long "
                        "sequences on one 16G chip)")
    p.add_argument("--decode", action="store_true",
                   help="also measure KV-cache generation tokens/sec")
    p.add_argument("--parallelism", default=None,
                   help="'dp,tp,pp' decomposition over the local "
                        "devices; pp > 1 runs the MPMD pipeline "
                        "runtime (docs/parallelism.md)")
    p.add_argument("--pipeline-schedule", default="1f1b",
                   choices=["gpipe", "1f1b", "interleaved"])
    p.add_argument("--microbatches", type=int, default=0,
                   help="microbatches per pipelined step (0 = auto)")
    p.add_argument("--cpu", type=int, default=0, metavar="N",
                   help="run on N virtual CPU devices (multi-device "
                        "pipeline smoke without a TPU)")
    p.add_argument("--sharded", action="store_true",
                   help="weight-update sharding: dp-shard the "
                        "optimizer state (make_lm_train_step("
                        "sharded=True); docs/parallelism.md)")
    p.add_argument("--config", default=None, choices=["lm2b"],
                   help="named model preset; lm2b is the multi-B-"
                        "param config that only fits with --sharded")
    p.add_argument("--overlap-compare", action="store_true",
                   help="A/B the compiled path's grouped vs bucket-"
                        "granular collective dispatch over hvd.run "
                        "rank threads (the ci.sh perf overlap gate); "
                        "composes with --parallelism dp,tp")
    p.add_argument("--overlap-bucket-bytes", type=int,
                   default=256 * 1024,
                   help="bucket ceiling for the bucketized leg of "
                        "--overlap-compare (0 would degenerate to "
                        "grouped)")
    p.add_argument("--overlap-embed-rows", type=int, default=4096,
                   help="embedding rows in the synthetic gradient set "
                        "of --overlap-compare (capped: the harness "
                        "measures dispatch overlap, not table "
                        "bandwidth)")
    p.add_argument("--overlap-compute-ms", type=float, default=2.0,
                   help="simulated backward compute burned per "
                        "gradient tensor in --overlap-compare")
    p.add_argument("--moe-experts", type=int, default=0,
                   help="run the lm-MoE loss-parity leg: train a "
                        "capacity-routed MoE config against its "
                        "dense-FLOP-matched baseline on identical "
                        "data (the ci.sh perf moe gate; "
                        "docs/parallelism.md 'Expert parallelism')")
    p.add_argument("--moe-capacity-factor", type=float, default=1.25,
                   help="per-expert slot headroom: capacity = "
                        "ceil(cf * tokens * topk / experts); "
                        "overflow drops deterministically")
    p.add_argument("--moe-topk", type=int, default=2,
                   help="experts each token routes to; the dense "
                        "baseline's FFN width is topk * d_ff_expert "
                        "so per-token FLOPs match")
    p.add_argument("--memory-budget-gb", type=float, default=None,
                   help="per-device memory budget for the fit gate "
                        "(default: the device's reported limit, else "
                        "16 — one TPUv3 core)")
    p.add_argument("--estimate-only", action="store_true",
                   help="print the memory verdict without training "
                        "(records the skip-vs-run asymmetry on "
                        "hosts that cannot run the big config)")
    args = p.parse_args()

    if args.config == "lm2b":
        # ~2.6B params: the post-436M headline config.  Dense adamw
        # needs ~31 GB/device (bf16 params+grads, f32 moments) and
        # SKIPS on a 16 GB budget; sharded at dp >= 4 fits — that
        # asymmetry is the memory evidence ISSUE 14 asks for.
        args.d_model, args.layers, args.heads = 2560, 32, 32
        args.seq = max(args.seq, 2048)
        args.remat = True

    if args.cpu:
        os.environ["HOROVOD_TPU_PLATFORM"] = "cpu"
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        # jax captured JAX_PLATFORMS at import; the config update is
        # what actually forces CPU on a TPU host (scaling.py idiom)
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)

    if args.overlap_compare:
        dp, tp, pp = parse_parallelism(args.parallelism) \
            if args.parallelism else (len(jax.devices()), 1, 1)
        if pp > 1:
            raise SystemExit(
                "--overlap-compare composes with dp/tp; the compiled "
                "path's overlap seam against pp is the reduce tick "
                "(docs/concepts.md), not this harness")
        out = {"d_model": args.d_model, "layers": args.layers,
               "parallelism": {"dp": dp, "tp": tp, "pp": 1}}
        out.update(bench_overlap(args, dp, tp))
        print(json.dumps(out))
        return

    if args.moe_experts:
        out = {"d_model": args.d_model, "layers": args.layers}
        out.update(bench_moe(args))
        print(json.dumps(out))
        return

    from horovod_tpu.models import TransformerConfig
    from horovod_tpu.parallel import (
        MeshSpec, PipelineSpec, build_mesh, bubble_fraction,
    )

    cfg = TransformerConfig(
        vocab_size=32000, d_model=args.d_model, n_layers=args.layers,
        n_heads=args.heads, d_ff=4 * args.d_model,
        max_seq_len=args.seq, dtype=jnp.bfloat16, remat=args.remat)
    pipeline = None
    if args.parallelism:
        dp, tp, pp = parse_parallelism(args.parallelism)
        if args.sharded and pp > 1:
            # the sharded dp hop lives on the MpmdWorker (engine)
            # substrate — ci.sh pp runs that parity config; the
            # single-process local pipeline runtime this bench uses
            # for pp keeps dense updates, and silently ignoring the
            # flag would record a sharded row that is not one
            raise SystemExit(
                "--sharded composes with dp/tp here; for sharded "
                "dp×pp use the multi-process MpmdWorker substrate "
                "(tools/pp_smoke.py / ci.sh pp)")
        mesh = build_mesh(MeshSpec(dp=dp, tp=tp, pp=pp),
                          jax.devices()[: dp * tp * pp])
        if pp > 1:
            pipeline = PipelineSpec(pp=pp, dp=dp, tp=tp,
                                    n_micro=args.microbatches,
                                    schedule=args.pipeline_schedule)
            r = pipeline.resolved()
            out_pp = {"parallelism": {"dp": dp, "tp": tp, "pp": pp},
                      "pipeline_schedule": r.schedule,
                      "n_microbatches": r.n_micro,
                      "bubble_fraction": round(bubble_fraction(
                          r.schedule, pp, r.n_micro, r.chunks), 4)}
        else:
            out_pp = {"parallelism": {"dp": dp, "tp": tp, "pp": pp}}
    else:
        mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
        out_pp = {}
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.seq), 0, cfg.vocab_size)

    out = {"batch": args.batch, "seq": args.seq,
           "d_model": args.d_model, "layers": args.layers, **out_pp}
    # -- memory fit gate:
    # big configs must SKIP with a clear verdict when the dense
    # optimizer cannot fit, and run (or at least fit) sharded — the
    # asymmetry is the memory evidence.
    n_params = lm_param_count(cfg.vocab_size, args.d_model,
                              args.layers, 4 * args.d_model)
    dp_total = int(np.prod(mesh.devices.shape)) if args.parallelism \
        else 1
    budget = args.memory_budget_gb
    if budget is None:
        budget = device_budget_gb()
    pbytes = 2 if cfg.dtype == jnp.bfloat16 else 4
    need_gb, fits = memory_verdict(n_params, dp_total, budget,
                                   param_bytes=pbytes,
                                   sharded=args.sharded)
    out.update(n_params=n_params, sharded=bool(args.sharded),
               memory_budget_gb=round(budget, 1),
               est_need_gb_per_device=round(need_gb, 1))
    if args.config == "lm2b" or args.estimate_only:
        if not fits:
            out["skipped"] = (
                f"{'sharded' if args.sharded else 'unsharded'} "
                f"adamw needs ~{need_gb:.1f} GB/device for "
                f"{n_params / 1e9:.2f}B params, budget is "
                f"{budget:.1f} GB"
                + ("" if args.sharded else
                   " — re-run with --sharded to split the optimizer "
                   "state ÷dp"))
            print(json.dumps(out))
            return
        if args.estimate_only:
            out["would_run"] = True
            print(json.dumps(out))
            return
    for impl in args.impls.split(","):
        impl = impl.strip()
        # "dense" = the default XLA S^2 softmax path ("ring" without
        # sequence_parallel is the single-shard dense fallback)
        tps, loss = bench_impl("ring" if impl == "dense" else impl,
                               cfg, tokens, mesh, args.iters,
                               args.warmup, pipeline=pipeline,
                               sharded=args.sharded)
        out[f"{impl}_tokens_per_sec"] = round(tps, 1)
        out[f"{impl}_loss"] = round(loss, 4)
    if "flash_tokens_per_sec" in out and "dense_tokens_per_sec" in out:
        out["flash_speedup"] = round(
            out["flash_tokens_per_sec"] / out["dense_tokens_per_sec"], 3)

    if args.decode and args.seq > 9:
        from horovod_tpu.models import TransformerLM, make_generate_fn
        model = TransformerLM(cfg)
        params = model.init(jax.random.PRNGKey(9),
                            tokens[:, :8])["params"]
        new = min(128, args.seq - 8)
        gen = make_generate_fn(model, max_new_tokens=new)
        gen(params, tokens[:, :8])            # compile prefill + step
        t0 = time.perf_counter()
        res = gen(params, tokens[:, :8])
        res.block_until_ready() if hasattr(res, "block_until_ready") \
            else None
        import numpy as _np
        _np.asarray(res)                      # value-forcing sync
        dt = time.perf_counter() - t0
        out["decode_tokens_per_sec"] = round(
            args.batch * new / dt, 1)
        out["decode_new_tokens"] = new
    elif args.decode:
        out["decode_skipped"] = "seq too short for an 8-token prompt"
    print(json.dumps(out))


if __name__ == "__main__":
    from horovod_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    main()
