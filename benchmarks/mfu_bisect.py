#!/usr/bin/env python
"""Bisect the big-LM step time into components on one chip.

Times, per variant, the full train step (fwd+bwd+adamw) through
``make_lm_train_step`` and prints tok/s + model TFLOP/s (MFU
convention: 6*N_matmul + causal-attention FLOPs, NO remat recompute
credit) so the expensive part is attributable.

    python benchmarks/mfu_bisect.py --variants base,novocab,dense
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

# the ONE definition of the MFU FLOPs convention — shared with the
# headline bench so the two cannot drift apart
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from lm_mfu_bench import lm_train_flops_per_token as model_flops_per_token  # noqa: E402,E501
from lm_mfu_bench import published_peak_tflops  # noqa: E402


def time_step(cfg, mesh, tokens, impl, iters, warmup,
              fused_ce=False, optimizer=None):
    from horovod_tpu.parallel import make_lm_train_step
    init, _, jit_step, tok_shd = make_lm_train_step(
        mesh, cfg, optimizer=optimizer or optax.adamw(1e-3),
        attention_impl=impl, fused_ce=fused_ce)
    state = init(jax.random.PRNGKey(0), tokens)
    compiled, state = jit_step(state)
    toks = jax.device_put(tokens, tok_shd)
    for _ in range(warmup):
        state, loss = compiled(state, toks)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = compiled(state, toks)
    float(loss)
    dt = time.perf_counter() - t0
    return tokens.size * iters / dt


def time_fwd_only(cfg, tokens, iters, warmup, fused_ce=True):
    """Forward loss only (no grad, no optimizer) at the model shapes —
    splits the step cost into fwd vs bwd+update."""
    from horovod_tpu.models import TransformerLM, make_fused_lm_loss, \
        lm_loss
    from horovod_tpu.ops.pallas_kernels import flash_attention

    model = TransformerLM(cfg, attention_fn=flash_attention)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 tokens)["params"]
    if fused_ce:
        loss_fn = jax.jit(make_fused_lm_loss(model))
    else:
        loss_fn = jax.jit(lambda p, t: lm_loss(
            model.apply({"params": p}, t)[:, :-1], t[:, 1:]))
    for _ in range(warmup):
        loss = loss_fn(params, tokens)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = loss_fn(params, tokens)
    float(loss)
    dt = time.perf_counter() - t0
    return tokens.size * iters / dt


def time_attn_only(cfg, B, iters):
    """Standalone flash fwd+bwd at the model's shapes, scanned in-jit."""
    from horovod_tpu.ops.pallas_kernels import flash_attention
    S, H, D = cfg.max_seq_len, cfg.n_heads, cfg.head_dim
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D),
                          jnp.bfloat16)

    def one(q):
        def loss(q):
            return jnp.sum(flash_attention(q, q, q).astype(jnp.float32))
        return jax.grad(loss)(q)

    @jax.jit
    def loop(q):
        def body(carry, _):
            return carry + 1e-6 * one(q), None
        out, _ = jax.lax.scan(body, q, None, length=iters)
        return jnp.sum(out.astype(jnp.float32))

    float(loop(q))                     # compile + run once
    t0 = time.perf_counter()
    float(loop(q))
    dt = time.perf_counter() - t0
    return B * S * iters / dt


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--d-model", type=int, default=1024)
    p.add_argument("--layers", type=int, default=24)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--vocab", type=int, default=32000)
    p.add_argument("--iters", type=int, default=8)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--variants",
                   default="base,novocab,dense,noremat,attn")
    p.add_argument("--remat-policy", default="full",
                   help="policy for remat variants (headline sweep: "
                        "dots_flash)")
    p.add_argument("--fused-ce", action="store_true",
                   help="fused chunked CE in every step variant "
                        "(the headline objective)")
    args = p.parse_args()

    from horovod_tpu.models import TransformerConfig
    from horovod_tpu.parallel import MeshSpec, build_mesh

    def cfg_for(vocab, remat, policy=None):
        return TransformerConfig(
            vocab_size=vocab, d_model=args.d_model,
            n_layers=args.layers, n_heads=args.heads,
            d_ff=4 * args.d_model, max_seq_len=args.seq,
            dtype=jnp.bfloat16, remat=remat,
            remat_policy=policy or args.remat_policy)

    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
    base_cfg = cfg_for(args.vocab, True)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.seq), 0, 2000)

    fpt = model_flops_per_token(base_cfg)
    device_kind = jax.devices()[0].device_kind
    peak = published_peak_tflops(device_kind)
    out = {"flops_per_token_g": round(fpt / 1e9, 3),
           "device_kind": device_kind, "published_peak_tflops": peak}
    for v in args.variants.split(","):
        v = v.strip()
        try:
            if v == "base":
                tps = time_step(base_cfg, mesh, tokens, "flash",
                                args.iters, args.warmup,
                                fused_ce=args.fused_ce)
            elif v == "novocab":
                tps = time_step(cfg_for(2048, True), mesh, tokens,
                                "flash", args.iters, args.warmup,
                                fused_ce=args.fused_ce)
            elif v == "dense":
                tps = time_step(base_cfg, mesh, tokens, "ring",
                                args.iters, args.warmup,
                                fused_ce=args.fused_ce)
            elif v == "noremat":
                tps = time_step(cfg_for(args.vocab, False), mesh,
                                tokens, "flash", args.iters,
                                args.warmup, fused_ce=args.fused_ce)
            elif v == "sgd":
                # optimizer-traffic probe: adamw reads+writes m/v/p
                # (f32, ~12 GB/step at 436M params); plain sgd reads
                # p + g and writes p — the delta is adam's HBM cost
                tps = time_step(base_cfg, mesh, tokens, "flash",
                                args.iters, args.warmup,
                                fused_ce=args.fused_ce,
                                optimizer=optax.sgd(1e-3))
            elif v == "fwd":
                tps = time_fwd_only(base_cfg, tokens, args.iters,
                                    args.warmup,
                                    fused_ce=args.fused_ce)
            elif v == "attn":
                tps = time_attn_only(base_cfg, args.batch, args.iters)
                out["attn_tokens_per_sec"] = round(tps, 1)
                continue
            else:
                continue
        except Exception as e:  # noqa: BLE001
            out[f"{v}_error"] = str(e)[:200]
            continue
        vf = model_flops_per_token(
            cfg_for(2048 if v == "novocab" else args.vocab, True))
        if v == "fwd":
            vf /= 3.0       # forward-only is 2N of the 6N convention
        out[f"{v}_tokens_per_sec"] = round(tps, 1)
        out[f"{v}_tflops"] = round(tps * vf / 1e12, 2)
        out[f"{v}_mfu_vs_published_peak_pct"] = round(
            100 * tps * vf / 1e12 / peak, 1)
    print(json.dumps(out))


if __name__ == "__main__":
    from horovod_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    main()
