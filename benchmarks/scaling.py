#!/usr/bin/env python
"""Multi-chip weak-scaling efficiency harness.

The reference's headline claim is *scaling efficiency* — 90% on 512
GPUs for ResNet-101/Inception-V3, 68% for VGG-16
(``/root/reference/docs/benchmarks.rst:8-14``), measured by running the
same per-device batch at increasing device counts.  This harness
reproduces that protocol for the TPU build: for each device count N it
builds a ``dp=N`` mesh, compiles the data-parallel train step (the
gradient psum rides ICI), measures steady-state throughput, and reports

    efficiency(N) = throughput(N) / (N * throughput(1))

Run on a pod slice it measures true ICI scaling; with ``--virtual N``
it runs on N virtual CPU devices (the only option on this 1-chip
driver) which validates the harness + sharding end-to-end, not absolute
performance.

Usage:
    python benchmarks/scaling.py                  # real devices 1..all
    python benchmarks/scaling.py --virtual 8      # 8 virtual CPU devices
    python benchmarks/scaling.py --model resnet   # flagship conv model
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--virtual", type=int, default=0,
                   help="use N virtual CPU devices instead of real chips")
    p.add_argument("--model",
                   choices=("transformer", "resnet", "resnet101",
                            "vgg16", "inception3", "vit_b16"),
                   default="transformer")
    p.add_argument("--batch-per-device", type=int, default=0,
                   help="per-device batch (default: model-specific)")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3,
                   help="warmup iterations (min 1: the first call also "
                        "binds the timed loop's state)")
    p.add_argument("--counts", type=str, default="",
                   help="comma-separated device counts (default: powers "
                        "of two up to the device total)")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    args.warmup = max(args.warmup, 1)   # the loops bind `loss`

    import jax
    if args.virtual:
        # must precede any backend use
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.virtual)
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu.parallel import MeshSpec, build_mesh
    from horovod_tpu.parallel.train import (
        make_dp_train_step, make_lm_train_step,
    )

    devices = jax.devices()
    total = len(devices)
    if args.counts:
        counts = [int(c) for c in args.counts.split(",")]
    else:
        counts = [n for n in (1, 2, 4, 8, 16, 32, 64, 128, 256)
                  if n <= total]
    on_cpu = devices[0].platform == "cpu"

    if args.model == "transformer":
        from horovod_tpu.models import TransformerConfig
        bpd = args.batch_per_device or (4 if on_cpu else 16)
        cfg = TransformerConfig(
            vocab_size=1024 if on_cpu else 32000,
            d_model=128 if on_cpu else 1024,
            n_layers=2 if on_cpu else 12,
            n_heads=4 if on_cpu else 16,
            d_ff=256 if on_cpu else 4096,
            max_seq_len=128 if on_cpu else 1024,
            dtype=jnp.float32 if on_cpu else jnp.bfloat16)

        def run_one(n):
            mesh = build_mesh(MeshSpec(dp=n), devices[:n])
            init, _, jit_step, tok_shd = make_lm_train_step(
                mesh, cfg, optimizer=optax.sgd(0.01))
            tokens = jax.random.randint(
                jax.random.PRNGKey(0), (bpd * n, cfg.max_seq_len), 0,
                cfg.vocab_size)
            state = init(jax.random.PRNGKey(1), tokens)
            compiled, state = jit_step(state)
            tok = jax.device_put(tokens, tok_shd)
            for _ in range(args.warmup):
                state, loss = compiled(state, tok)
            jax.block_until_ready(loss)
            t0 = time.perf_counter()
            for _ in range(args.iters):
                state, loss = compiled(state, tok)
            jax.block_until_ready(loss)
            dt = time.perf_counter() - t0
            return bpd * n * args.iters / dt      # sequences/sec
    else:
        from horovod_tpu.models import (
            InceptionV3, ResNet50, ResNet101, VGG16, ViT_B16,
        )
        factory = {"resnet": ResNet50, "resnet101": ResNet101,
                   "vgg16": VGG16, "inception3": InceptionV3,
                   "vit_b16": ViT_B16}[args.model]
        bpd = args.batch_per_device or (8 if on_cpu else 128)
        factory_kwargs = {}
        if args.model == "inception3":
            # the stem's VALID convs need >= ~75px to survive
            img_size = 96 if on_cpu else 299
        elif args.model == "vit_b16":
            img_size = 96 if on_cpu else 224   # multiple of patch 16
            # pos embeddings are sized from the configured image size
            factory_kwargs["image_size"] = img_size
        else:
            img_size = 64 if on_cpu else 224
        model = factory(num_classes=100 if on_cpu else 1000,
                        **factory_kwargs)

        def run_one(n):
            mesh = build_mesh(MeshSpec(dp=n), devices[:n])
            images = jax.random.normal(
                jax.random.PRNGKey(0),
                (bpd * n, img_size, img_size, 3), cfg_dtype)
            labels = jax.random.randint(
                jax.random.PRNGKey(1), (bpd * n,), 0,
                100 if on_cpu else 1000)
            variables = model.init(jax.random.PRNGKey(2), images[:1],
                                   train=False)

            def loss_fn(out, labels):
                logp = jax.nn.log_softmax(out[0] if isinstance(out, tuple)
                                          else out)
                return -jnp.mean(jnp.take_along_axis(
                    logp, labels[:, None], axis=-1))

            def apply_fn(vars_, batch):
                return model.apply(vars_, batch, train=False)

            state = {"params": variables["params"],
                     "extra": {"batch_stats":
                               variables.get("batch_stats", {})},
                     "opt_state": optax.sgd(0.1).init(variables["params"]),
                     "step": jnp.zeros((), jnp.int32)}
            _, jit_step = make_dp_train_step(
                mesh, apply_fn, optax.sgd(0.1), loss_fn)
            compiled, state = jit_step(state)
            from jax.sharding import NamedSharding, PartitionSpec as P
            shd = NamedSharding(mesh, P(("dp", "fsdp")))
            img = jax.device_put(images, shd)
            lbl = jax.device_put(labels, shd)
            for _ in range(args.warmup):
                state, loss = compiled(state, img, lbl)
            jax.block_until_ready(loss)
            t0 = time.perf_counter()
            for _ in range(args.iters):
                state, loss = compiled(state, img, lbl)
            jax.block_until_ready(loss)
            dt = time.perf_counter() - t0
            return bpd * n * args.iters / dt      # images/sec

        cfg_dtype = jnp.float32 if on_cpu else jnp.bfloat16

    results = []
    base_per_dev = None
    for n in counts:
        tput = run_one(n)
        if base_per_dev is None:
            base_per_dev = tput / n
        eff = tput / (n * base_per_dev)
        results.append({"devices": n, "throughput": round(tput, 2),
                        "efficiency": round(eff, 4)})
        print(json.dumps({"metric": f"scaling_{args.model}",
                          **results[-1]}), flush=True)
    print(json.dumps({
        "metric": f"scaling_efficiency_{args.model}",
        "value": results[-1]["efficiency"],
        "unit": f"fraction at {results[-1]['devices']} devices",
        "vs_baseline": round(results[-1]["efficiency"] / 0.90, 3),
    }))
    return results


if __name__ == "__main__":
    from horovod_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    main()
