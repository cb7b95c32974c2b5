#!/usr/bin/env python
"""Chip smoke: the quickest proof that horovod_tpu still starts on the
chip.

    python chip_smoke.py             # one chip — what the driver runs
    python chip_smoke.py --chips 4   # the multi-chip paths, one 4-chip host

One chip: a few eager collectives through the engine (two ranks stacked
on the chip) against numpy, then lm436m at full width (``HEADLINE``
below) through the user's entry points (``hvd.init``,
``hvd.make_compiled_train_step``, ``init_state``, ``place_batch``,
``step``, ``hvd.shutdown``) for ``STEPS`` steps on one fixed batch made
from ``SEED``.

Four chips (``--chips 4``, and nothing of the above): the eager
collectives at one rank per chip; the same lm436m step under
``hvd.run`` at four ranks and through ``make_lm_train_step`` on a
dp=2 x tp=2 mesh, each compared step by step with the one-chip step on
the same batch in the same process.

Every phase prints one JSON object; the last line of stdout is
``{"ok": ..., "device": {"platform", "kind", "count"}}``.  A failed
phase, a platform other than ``tpu``, or a flash kernel that did not
reach the compiler as a ``tpu_custom_call`` makes ``ok`` false and the
exit code 1.  Step times printed here are set-up information of a
smoke, not a benchmark.
"""

import argparse
import functools
import json
import math
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

SEED = 0
STEPS = 5               # one chip
STEPS_COMPARED = 3      # four chips: every path and its reference
# |loss - one-chip loss| per step.  The losses are f32 means over
# 10k tokens of bf16 activations: another program (shard_map, a tp
# split of every matmul's contraction) re-orders bf16 roundings, and
# three adamw steps carry the difference forward.
LOSS_ATOL = 0.01
# lm436m: ~436M parameters (402.7M in the blocks, 32.8M embedding),
# head_dim 128, SwiGLU, bf16, S=2048
HEADLINE = dict(vocab_size=32000, d_model=1024, n_layers=24, n_heads=8,
                d_ff=4096, max_seq_len=2048)
HEADLINE_BATCH = 5      # what 16 GB of HBM holds beside the step's temporaries
# rehearsal widths: every phase end to end on the CPU in seconds
TINY = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=2,
            d_ff=128, max_seq_len=128)
TINY_BATCH = 2


class SmokeFailure(Exception):
    pass


def check(cond, message):
    if not cond:
        raise SmokeFailure(message)


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def device_memory():
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append({k: stats.get(k) for k in
                    ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")})
    return out


# ---------------------------------------------------------------------------
# phases

def phase_environment(cache_dir):
    from importlib import metadata

    import jax
    import jaxlib

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    emit("environment", jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=metadata.version("libtpu"), device=device,
         compile_cache_dir=cache_dir)
    return device


def check_device(device, chips, rehearse):
    if not rehearse:
        check(device["platform"] == "tpu",
              f"platform is {device['platform']!r}, not 'tpu'")
    # (the one-chip path takes the first device whatever the count,
    # so its rehearsal also runs on the tests' eight virtual devices)
    check(device["count"] == chips or (rehearse and chips == 1),
          f"{device['count']} devices, this run needs {chips}")


def phase_native():
    from horovod_tpu.core import native

    status = native.status()
    emit("native", host_library=status)
    check(status in ("built", "loaded"),
          "the native host library neither built nor loaded")


def phase_eager(np_ranks):
    """allreduce / broadcast / allgather / alltoall through the engine
    against numpy, on every rank.  ``np_ranks=None`` is hvd.run's
    default, which must come out as one rank per chip."""
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.common.basics import engine

    def fn():
        r, n = hvd.rank(), hvd.size()
        base = np.arange(8, dtype=np.float32)
        got = hvd.allreduce(base + r, op=hvd.Average)
        np.testing.assert_allclose(got, base + (n - 1) / 2, rtol=1e-6)
        got = hvd.broadcast(np.full(5, r, np.int32), root_rank=n - 1)
        np.testing.assert_array_equal(got, np.full(5, n - 1, np.int32))
        got = hvd.allgather(np.full((2, 3), r, np.float32))
        np.testing.assert_array_equal(
            got, np.repeat(np.arange(n, dtype=np.float32), 2)[:, None]
            * np.ones((1, 3), np.float32))
        # rank r sends row block j (two rows of value 10 r + j) to j
        send = np.repeat(10.0 * r + np.arange(n, dtype=np.float32), 2)
        got, got_splits = hvd.alltoall(
            send[:, None] * np.ones((1, 3), np.float32))
        want = np.repeat(10.0 * np.arange(n, dtype=np.float32) + r, 2)
        np.testing.assert_array_equal(
            got, want[:, None] * np.ones((1, 3), np.float32))
        np.testing.assert_array_equal(got_splits, np.full(n, 2))
        ex = engine().process_sets[0].executor
        return n, ex.shard_mode, sorted(str(d) for d in set(ex.devices))

    results = hvd.run(fn, np=np_ranks)
    n, shard_mode, devices = results[0]
    emit("eager", ranks=n, shard_mode=shard_mode, devices=devices,
         collectives=["allreduce", "broadcast", "allgather", "alltoall"],
         match_numpy=True)
    check(len(results) == n, "a rank returned nothing")
    if np_ranks is None:
        check(shard_mode and len(devices) == n,
              f"{n} ranks on devices {devices}, shard_mode={shard_mode}:"
              f" not one rank per chip")


def build(batch, widths=HEADLINE):
    """(config, fixed token batch) of lm436m, or of ``widths``."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerConfig

    cfg = TransformerConfig(dtype=jnp.bfloat16, remat=True,
                            remat_policy="dots_flash", **widths)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, cfg.max_seq_len), 0,
        cfg.vocab_size)
    return cfg, tokens


def model_and_loss(cfg, attention_fn=None):
    """(model, loss_fn(params, tokens)): the logits projection fused
    into a chunked loss, so the (B, S, V) float32 logits never exist.
    Shared with tests/test_chip_compile.py so both train the same
    program.  ``attention_fn`` defaults to the Pallas flash kernel."""
    from horovod_tpu.models import TransformerLM, make_fused_lm_loss
    from horovod_tpu.ops.pallas_kernels import flash_attention

    model = TransformerLM(cfg, attention_fn=attention_fn
                          or flash_attention)
    return model, make_fused_lm_loss(model, n_chunks=16)


def lm_setup(rehearse):
    """(config, fixed batch, attention kernel) of lm436m; the rehearsal
    swaps in tiny widths and asks for the kernels' interpret mode."""
    from horovod_tpu.ops.pallas_kernels import flash_attention

    if rehearse:
        cfg, tokens = build(TINY_BATCH, TINY)
        return cfg, tokens, functools.partial(flash_attention,
                                              interpret=True)
    cfg, tokens = build(HEADLINE_BATCH)
    return cfg, tokens, None        # the flash kernel's own default


def lm_describe(cfg, tokens):
    return {"d_model": cfg.d_model, "n_layers": cfg.n_layers,
            "n_heads": cfg.n_heads, "head_dim": cfg.head_dim,
            "d_ff": cfg.d_ff, "vocab": cfg.vocab_size,
            "seq": cfg.max_seq_len, "batch": int(tokens.shape[0]),
            "dtype": "bfloat16", "remat": cfg.remat_policy}


def lm_loss_and_params(cfg, tokens, attn):
    import jax

    model, loss_fn = model_and_loss(cfg, attn)
    params = jax.jit(model.init)(jax.random.PRNGKey(SEED),
                                 tokens)["params"]
    return loss_fn, params


def check_losses(losses, vocab):
    check(all(math.isfinite(v) for v in losses),
          f"non-finite loss: {losses}")
    check(abs(losses[0] - math.log(vocab)) < 0.5,
          f"first loss {losses[0]} is not near ln({vocab}) = "
          f"{math.log(vocab):.2f}")
    check(losses[-1] < losses[0],
          f"loss did not fall on the fixed batch: {losses}")


def phase_train_one_chip(cfg, tokens, attn, steps, rehearse):
    """The main path: hvd.init() (one rank on the first chip) and the
    compiled train step.  Returns the per-step losses."""
    import jax
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import telemetry

    hvd.init()
    try:
        loss_fn, params = lm_loss_and_params(cfg, tokens, attn)
        n_params = sum(p.size for p in jax.tree.leaves(params))
        step = hvd.make_compiled_train_step(loss_fn, optax.adamw(1e-3))
        state = step.init_state(params)
        del params              # the step donates the state's buffers
        staged = step.place_batch(tokens)
        t0 = time.perf_counter()
        state, loss = step(state, staged)
        losses = [float(loss)]              # fetching the value syncs
        first_step = time.perf_counter() - t0
        compile_seconds = telemetry.counter_total(
            "horovod_compile_seconds_total")
        # the first call by stage, and what the persistent compile
        # cache did in it (the program's own listener)
        stages = {stage: round(telemetry.counter_total(
            f"horovod_compile_{stage}_seconds_total"), 2)
            for stage in ("trace", "lower", "backend", "cache_read")}
        cache_hits, cache_writes = (int(telemetry.counter_total(
            f"horovod_compile_cache_{what}_total"))
            for what in ("hits", "writes"))
        misses = telemetry.counter_total(
            "horovod_program_cache_misses_total")
        step_seconds = []
        for _ in range(steps - 1):
            t0 = time.perf_counter()
            state, loss = step(state, staged)
            losses.append(float(loss))
            step_seconds.append(round(time.perf_counter() - t0, 4))
        misses_after = telemetry.counter_total(
            "horovod_program_cache_misses_total") - misses
        custom_calls = step.lower(state, staged).as_text() \
            .count("tpu_custom_call")
        memory = device_memory()[0]
    finally:
        hvd.shutdown()
    emit("train", entry="hvd.make_compiled_train_step",
         model="rehearsal" if rehearse else "lm436m",
         **lm_describe(cfg, tokens), params=int(n_params), steps=steps,
         losses=[round(v, 4) for v in losses],
         compile_seconds=round(compile_seconds, 2),
         compile_stage_seconds=stages,
         step_compile="cache-hit" if cache_hits and not cache_writes
         else "compiled",
         compile_cache={"hits": cache_hits, "writes": cache_writes},
         first_step_seconds=round(first_step, 2),
         step_seconds_of_a_smoke_not_a_benchmark=step_seconds,
         tpu_custom_calls=custom_calls,
         program_cache_misses_after_warmup=int(misses_after), **memory)
    check_losses(losses, cfg.vocab_size)
    check(misses_after == 0,
          f"{misses_after} program-cache misses after the first step")
    if not rehearse:
        check(custom_calls > 0, "no tpu_custom_call in the step: the "
              "flash kernel did not reach the chip compiler")
    return losses


def check_spread_over_chips(phase, params, n_chips, rehearse):
    """Code that has only seen one chip may put everything on the
    first: every device holds bytes of the same order, and the
    parameters live on all of them."""
    import jax

    memory = device_memory()
    device_sets = {len(p.sharding.device_set)
                   for p in jax.tree.leaves(params)}
    emit(phase + ".placement", per_device=memory,
         param_device_set_sizes=sorted(device_sets))
    check(device_sets == {n_chips},
          f"parameters on {sorted(device_sets)} devices, not {n_chips}")
    in_use = [m["bytes_in_use"] for m in memory]
    if rehearse and None in in_use:
        return          # the CPU backend reports no memory statistics
    check(None not in in_use and min(in_use) > 0
          and max(in_use) <= 4 * min(in_use),
          f"bytes_in_use differ across devices: {in_use}")


def compare_with_one_chip(phase, losses, reference, **fields):
    diffs = [abs(a - b) for a, b in zip(losses, reference)]
    emit(phase, losses=[round(v, 4) for v in losses],
         one_chip_losses=[round(v, 4) for v in reference],
         abs_diff=[round(d, 5) for d in diffs], atol=LOSS_ATOL, **fields)
    check(len(losses) == len(reference) and max(diffs) <= LOSS_ATOL,
          f"{phase}: losses {losses} differ from the one-chip step's "
          f"{reference} by more than {LOSS_ATOL}")


def phase_data_parallel(cfg, tokens, attn, reference, rehearse):
    """The compiled step under hvd.run at one rank per chip, every
    rank fed the same fixed batch: the averaged gradient is the
    one-chip gradient."""
    import jax
    import numpy as np
    import optax

    import horovod_tpu as hvd

    loss_fn, params = lm_loss_and_params(cfg, tokens, attn)
    # to the host: the ranks' one replicated state is built from it,
    # and no second copy of the parameters stays on the first chip
    params = jax.device_get(params)
    batch = np.asarray(tokens)
    n_chips = len(jax.devices())

    def fn():
        step = hvd.make_compiled_train_step(loss_fn, optax.adamw(1e-3))
        state = step.init_state(params)
        losses = []
        for _ in range(len(reference)):
            state, loss = step(state, batch)
            losses.append(float(loss))
        if hvd.rank() == 0:
            check_spread_over_chips("data_parallel", state["params"],
                                    n_chips, rehearse)
        return losses

    results = hvd.run(fn)
    check(len(results) == n_chips and
          all(r == results[0] for r in results),
          f"ranks disagree on the loss: {results}")
    compare_with_one_chip("data_parallel", results[0], reference,
                          entry="hvd.run + hvd.make_compiled_train_step",
                          ranks=len(results))


def phase_spmd(cfg, tokens, reference, rehearse):
    """make_lm_train_step on a dp=2 x tp=2 mesh; each dp half holds
    the fixed batch, so the mean loss and gradient are the one-chip
    step's."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.parallel import build_mesh, make_lm_train_step

    mesh = build_mesh(dp=2, tp=2)
    init, _, jit_step, tok_sharding = make_lm_train_step(
        mesh, cfg, optimizer=optax.adamw(1e-3), attention_impl="flash",
        fused_ce=True)
    both = jnp.concatenate([tokens, tokens])
    state = jax.jit(init)(jax.random.PRNGKey(SEED), both)
    compiled, state = jit_step(state)
    both = jax.device_put(both, tok_sharding)
    custom_calls = compiled.lower(state, both).as_text() \
        .count("tpu_custom_call")
    losses = []
    for _ in range(len(reference)):
        state, loss = compiled(state, both)
        losses.append(float(loss))
    check_spread_over_chips("spmd", state["params"], mesh.size, rehearse)
    compare_with_one_chip("spmd", losses, reference,
                          entry="make_lm_train_step(dp=2, tp=2, flash, "
                          "fused_ce)", tpu_custom_calls=custom_calls)
    if not rehearse:
        check(custom_calls > 0, "no tpu_custom_call in the SPMD step")


# ---------------------------------------------------------------------------

def run(chips=1, rehearse=False):
    """Run the smoke; returns the exit code.  ``rehearse`` is for the
    CPU tests: tiny widths, interpret-mode kernels, no TPU demanded."""
    device = None
    try:
        from horovod_tpu.utils.compile_cache import place_compile_cache

        # (a rehearsal leaves its process's cache setting alone: the
        # tests call it in-process)
        cache_dir = None if rehearse else place_compile_cache()
        device = phase_environment(cache_dir)
        check_device(device, chips, rehearse)
        phase_native()
        cfg, tokens, attn = lm_setup(rehearse)
        if chips == 1:
            phase_eager(np_ranks=2)
            phase_train_one_chip(cfg, tokens, attn, STEPS, rehearse)
        else:
            phase_eager(np_ranks=None)
            reference = phase_train_one_chip(cfg, tokens, attn,
                                             STEPS_COMPARED, rehearse)
            phase_data_parallel(cfg, tokens, attn, reference, rehearse)
            phase_spmd(cfg, tokens, reference, rehearse)
        ok = True
    except Exception as exc:  # noqa: BLE001 — any failed phase fails
        # the smoke; the traceback goes to stderr, the verdict to stdout
        traceback.print_exc()
        emit("failed", error=f"{type(exc).__name__}: {exc}"[:2000])
        ok = False
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the multi-chip paths and their "
                             "one-chip comparison (one 4-chip host)")
    args = parser.parse_args(argv)
    return run(chips=args.chips)


if __name__ == "__main__":
    sys.exit(main())
