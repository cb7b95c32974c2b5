#!/usr/bin/env python
"""Headline LM benchmark: a 436M-param decoder trained THROUGH the
framework's compiled train step, reported as tok/s and MFU.

The reference's headline protocol is synthetic throughput through
``DistributedOptimizer`` (``docs/benchmarks.rst:15-63``); this is the
same idea on the matmul-dominated workload TPUs are built for: a
properly-sized Transformer (d_model 1024, 24 layers, head_dim 128,
SwiGLU d_ff 4096, vocab 32k, S=2048, bf16, dots_flash remat — save
matmul + flash-kernel outputs, replay only cheap glue — pallas flash
attention, chunked fused cross-entropy) through
``hvd.make_compiled_train_step`` — engine up,
process set 0's executor staging, fwd+bwd+reduce+update as one XLA
program.

MFU convention: model FLOPs = 6 * (matmul params incl. the logits
projection) + causal attention matmuls, with NO credit for remat
recompute — divided by the PUBLISHED bf16 peak of the device the run
is on (``PUBLISHED_PEAK_TFLOPS``, keyed by ``device_kind``).

    python benchmarks/lm_mfu_bench.py
    python benchmarks/lm_mfu_bench.py --raw   # plain-jit ceiling too
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Published dense bf16 peak of one chip, keyed by
# ``jax.devices()[0].device_kind``.  A device that is not here is an
# error, never a default: add it with its source.
PUBLISHED_PEAK_TFLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197.0,
}


def published_peak_tflops(device_kind):
    try:
        return PUBLISHED_PEAK_TFLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device_kind {device_kind!r}: add "
            f"it to PUBLISHED_PEAK_TFLOPS with its source") from None


# headline config: ~436M params (402.7M block + 32.8M embedding)
HEADLINE = dict(vocab_size=32000, d_model=1024, n_layers=24, n_heads=8,
                d_ff=4096, max_seq_len=2048)
HEADLINE_BATCH = 5                    # best measured on 16G HBM


def lm_train_flops_per_token(cfg):
    """MFU-convention FLOPs/token: 6*(block + logits matmul params) +
    fwd/bwd causal-attention matmuls; remat recompute NOT counted."""
    d, f, L, V = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab_size
    n_block = L * (4 * d * d + 3 * d * f)
    n_logits = V * d
    attn = 6 * L * cfg.max_seq_len * d * 0.5    # causal halves it
    return 6 * (n_block + n_logits) + attn


def build(args, widths=HEADLINE):
    """(config, fixed token batch) of the headline model; ``widths``
    swaps in a small model for a rehearsal (chip_smoke.py's CPU
    test)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerConfig

    remat = getattr(args, "remat", "dots_flash")
    cfg = TransformerConfig(dtype=jnp.bfloat16, remat=remat != "none",
                            remat_policy=remat if remat != "none"
                            else "full", **widths)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, cfg.max_seq_len), 0,
        cfg.vocab_size)
    return cfg, tokens


def model_and_loss(cfg, attention_fn=None, fused_ce=True, ce_chunks=16):
    """(model, loss_fn(params, tokens)) of the headline objective —
    shared with chip_smoke.py and tests/test_chip_compile.py so all
    three train the same program.  ``attention_fn`` defaults to the
    Pallas flash kernel."""
    from horovod_tpu.models import TransformerLM, lm_loss, \
        make_fused_lm_loss
    from horovod_tpu.ops.pallas_kernels import flash_attention

    model = TransformerLM(cfg, attention_fn=attention_fn
                          or flash_attention)
    if fused_ce:
        # logits projection fused into a chunked loss: the (B, S, V)
        # f32 logits + log-softmax (2.6 GB at B=5) never exist —
        # the SAME objective make_lm_train_step(fused_ce=True) builds
        return model, make_fused_lm_loss(model, n_chunks=ce_chunks)

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch)
        return lm_loss(logits[:, :-1], batch[:, 1:])
    return model, loss_fn


def bench_framework(cfg, tokens, iters, warmup, fused_ce=True,
                    ce_chunks=16, bwd_block=None):
    """Through hvd.make_compiled_train_step (the user path)."""
    import functools

    import jax
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.ops.pallas_kernels import flash_attention

    hvd.init()
    attn = None if bwd_block is None else functools.partial(
        flash_attention, bwd_block_q=bwd_block, bwd_block_k=bwd_block)
    model, loss_fn = model_and_loss(cfg, attn, fused_ce, ce_chunks)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 tokens)["params"]
    step = hvd.make_compiled_train_step(loss_fn, optax.adamw(1e-3))
    state = step.init_state(params)
    staged = step.place_batch(tokens)
    for _ in range(warmup):
        state, loss = step(state, staged)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = step(state, staged)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    lv = float(loss)
    hvd.shutdown()
    return tokens.size * iters / dt, lv


def bench_raw(cfg, tokens, iters, warmup, fused_ce=True):
    """Plain-jit ceiling (make_lm_train_step, no engine)."""
    import jax
    import optax

    from horovod_tpu.parallel import MeshSpec, build_mesh, \
        make_lm_train_step

    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
    init, _, jit_step, tok_shd = make_lm_train_step(
        mesh, cfg, optimizer=optax.adamw(1e-3),
        attention_impl="flash", fused_ce=fused_ce)
    state = init(jax.random.PRNGKey(0), tokens)
    compiled, state = jit_step(state)
    toks = jax.device_put(tokens, tok_shd)
    for _ in range(warmup):
        state, loss = compiled(state, toks)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = compiled(state, toks)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    return tokens.size * iters / dt


def make_report(tps, loss, cfg, n_chips=1):
    """The headline metric dict — shared by this CLI and bench.py so
    the MFU convention and metric key cannot drift apart.  Multi-chip
    runs (``--parallelism``) report PER-CHIP tok/s and MFU against
    the single-chip peak, so the number stays comparable to the
    headline.  The peak is the published one of the device the
    process runs on; a device without one fails the report."""
    import jax

    device_kind = jax.devices()[0].device_kind
    peak = published_peak_tflops(device_kind)
    fpt = lm_train_flops_per_token(cfg)
    per_chip = tps / max(n_chips, 1)
    out = {
        "metric": "lm436m_train_tokens_per_sec_per_chip_hvd",
        "value": round(per_chip, 1),
        "unit": "tokens/sec",
        "device_kind": device_kind,
        "loss": round(loss, 4),
        "model_tflops_per_sec": round(per_chip * fpt / 1e12, 2),
        "mfu_vs_published_peak_pct": round(
            100 * per_chip * fpt / 1e12 / peak, 1),
        "flops_per_token_g": round(fpt / 1e9, 3),
        "published_peak_tflops": peak,
    }
    if n_chips > 1:
        out["n_chips"] = n_chips
        out["total_tokens_per_sec"] = round(tps, 1)
    return out


def bench_pipelined(cfg, tokens, iters, warmup, parallelism,
                    schedule, n_micro):
    """Through make_lm_train_step(pipeline=...) — the MPMD dp×tp×pp
    runtime (docs/parallelism.md) with the flash attention kernel."""
    import jax
    import optax

    from horovod_tpu.parallel import (
        MeshSpec, PipelineSpec, build_mesh, make_lm_train_step,
    )

    dp, tp, pp = parallelism
    mesh = build_mesh(MeshSpec(dp=dp, tp=tp, pp=pp),
                      jax.devices()[: dp * tp * pp])
    spec = PipelineSpec(pp=pp, dp=dp, tp=tp, n_micro=n_micro,
                        schedule=schedule)
    init, step, _, tok_shd = make_lm_train_step(
        mesh, cfg, optimizer=optax.adamw(1e-3),
        attention_impl="flash", pipeline=spec)
    state = init(jax.random.PRNGKey(0), tokens)
    for _ in range(warmup):
        state, loss = step(state, tokens)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = step(state, tokens)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    return tokens.size * iters / dt, float(loss), spec.resolved()


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=HEADLINE_BATCH)
    p.add_argument("--iters", type=int, default=12)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--raw", action="store_true",
                   help="also measure the plain-jit ceiling")
    p.add_argument("--no-fused-ce", action="store_true",
                   help="unfused loss (materialize the full logits)")
    p.add_argument("--remat",
                   choices=["dots", "dots_flash", "full", "none"],
                   default="dots_flash",
                   help="remat policy sweep knob (headline: "
                        "dots_flash)")
    p.add_argument("--ce-chunks", type=int, default=16,
                   help="fused-CE sequence chunks (headline: 16)")
    p.add_argument("--flash-bwd-block", type=int, default=None,
                   help="independent flash BACKWARD kernel block size "
                        "(default: same as forward, 512)")
    p.add_argument("--parallelism", default=None,
                   help="'dp,tp,pp' decomposition over the local "
                        "devices; pp > 1 runs the headline model "
                        "through the MPMD pipeline runtime "
                        "(docs/parallelism.md)")
    p.add_argument("--pipeline-schedule", default="1f1b",
                   choices=["gpipe", "1f1b", "interleaved"])
    p.add_argument("--microbatches", type=int, default=0,
                   help="microbatches per pipelined step (0 = auto)")
    args = p.parse_args()

    cfg, tokens = build(args)
    if args.parallelism:
        from lm_bench import parse_parallelism

        from horovod_tpu.parallel import bubble_fraction

        dp, tp, pp = parse_parallelism(args.parallelism)
        tps, loss, spec = bench_pipelined(
            cfg, tokens, args.iters, args.warmup, (dp, tp, pp),
            args.pipeline_schedule, args.microbatches)
        out = make_report(tps, loss, cfg, n_chips=dp * tp * pp)
        out["parallelism"] = {"dp": dp, "tp": tp, "pp": pp}
        if pp > 1:
            out["pipeline_schedule"] = spec.schedule
            out["n_microbatches"] = spec.n_micro
            out["bubble_fraction"] = round(bubble_fraction(
                spec.schedule, pp, spec.n_micro, spec.chunks), 4)
        print(json.dumps(out))
        return
    tps, loss = bench_framework(cfg, tokens, args.iters, args.warmup,
                                fused_ce=not args.no_fused_ce,
                                ce_chunks=args.ce_chunks,
                                bwd_block=args.flash_bwd_block)
    out = make_report(tps, loss, cfg)
    if args.raw:
        raw = bench_raw(cfg, tokens, args.iters, args.warmup,
                        fused_ce=not args.no_fused_ce)
        out["raw_jax_tokens_per_sec"] = round(raw, 1)
        out["framework_fraction_of_raw"] = round(tps / raw, 4)
    print(json.dumps(out))


if __name__ == "__main__":
    from horovod_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    main()
