"""The routed layer's way back to the tokens, alone on the chip:
``parallel/moe._sum_by_owner`` behind a producer inside the program (the
table is ``out * w``, as the combine makes it, never an argument), at the
benchmark's two routed shapes and in the forms PR 35 weighed, each
called ``CALLS`` times under the profiler; device time by instruction
from the trace.

    python tools/sum_by_owner_probe.py            (on the chip, ~2 min)
    REHEARSE=1 python tools/sum_by_owner_probe.py (tiny shapes, here)

Writes ``chiprun_out/sum_by_owner_probe.json`` and prints a table.  The
forms: ``stood`` is the function as it stood before PR 35 (one gather
of all of ``rows`` over every slot, a masked float32 sum over the
slots); ``pad`` pads the slot table's minor axis to whole tiles of 8
with never-valid columns; ``split<p>`` gathers and sums ``p`` column
pieces of the table apart and joins the sums; ``both<p>`` does both;
``turned`` gathers with the slots as the LEADING axis, ``(K, T, M)``, and
sums over it (no tile is cut whatever K is, no row is added), ``turned<p>``
that in ``p`` column pieces; ``tree`` is the function the tree holds now.
"""

import json
import os
import shutil
import sys
from functools import partial

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CALLS = 24
REHEARSE = bool(os.environ.get("REHEARSE"))
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace", "sum_by_owner_probe")
OUT = os.path.join(ROOT, "chiprun_out", "sum_by_owner_probe%s.json"
                   % ("_rehearsal" if REHEARSE else ""))

# (tokens, experts a token, width, buffer rows, experts, held experts):
# the held share of the slots is valid (25% / 12.5% / 18.75%)
SHAPES = {
    "smallthinker": (16384, 6, 2560, 30720, 64, 16),    # 157 MB table
    "trinity": (16384, 8, 2048, 20480, 128, 16),        # 84 MB
    "k8-105MB": (16384, 8, 2560, 20480, 128, 16),
    "k8-126MB": (16384, 8, 2048, 30720, 128, 24),
}
if REHEARSE:
    SHAPES = {k: (64, v[1], 256, 256, 16, 4) for k, v in SHAPES.items()}
    CALLS = 2


def stood(rows, slot, valid):
    """``_sum_by_owner`` as it stood before PR 35 (the tests hold the
    tree's function against it where the form must not have changed)."""
    import jax.numpy as jnp

    picked = rows[jnp.where(valid, slot, 0)]
    return jnp.sum(jnp.where(valid[..., None], picked, 0), axis=1,
                   dtype=jnp.float32)


def pad(rows, slot, valid, inner=stood):
    import jax.numpy as jnp

    more = -slot.shape[1] % 8
    if more:
        slot = jnp.pad(slot, ((0, 0), (0, more)),
                       constant_values=rows.shape[0])
        valid = jnp.pad(valid, ((0, 0), (0, more)))
    return inner(rows, slot, valid)


def turned(rows, slot, valid):
    """The slots as the leading axis: ``(K, T, M)`` gathered, summed
    over axis 0, so no tile is cut whatever K is and no row is added."""
    import jax.numpy as jnp

    picked = rows[jnp.where(valid, slot, 0).T]
    return jnp.sum(jnp.where(valid.T[..., None], picked, 0), axis=0,
                   dtype=jnp.float32)


def split(rows, slot, valid, pieces=2, inner=stood):
    import jax.numpy as jnp

    width = rows.shape[1] // pieces
    return jnp.concatenate(
        [inner(rows[:, i * width:(i + 1) * width], slot, valid)
         for i in range(pieces)], axis=1)


def forms():
    from horovod_tpu.parallel import moe

    return {
        "stood": stood, "pad": pad, "split2": split,
        "both2": partial(split, inner=pad),
        "split4": partial(split, pieces=4),
        "both4": partial(split, pieces=4, inner=pad),
        "turned": turned, "turned2": partial(split, inner=turned),
        "turned4": partial(split, pieces=4, inner=turned),
        "tree": moe._sum_by_owner,
    }


PROBES = [("trinity", "stood", "bf16"), ("smallthinker", "stood", "bf16"),
          ("smallthinker", "pad", "bf16"), ("smallthinker", "split2", "bf16"),
          ("smallthinker", "both2", "bf16"), ("smallthinker", "split4", "bf16"),
          ("smallthinker", "both4", "bf16"), ("smallthinker", "stood", "f32"),
          ("smallthinker", "both2", "f32"), ("smallthinker", "turned", "bf16"),
          ("smallthinker", "turned2", "bf16"), ("smallthinker", "turned4", "bf16"),
          ("smallthinker", "turned2", "f32"), ("trinity", "turned", "bf16"),
          ("k8-105MB", "stood", "bf16"),
          ("k8-126MB", "stood", "bf16"), ("k8-126MB", "split2", "bf16"),
          ("smallthinker", "tree", "bf16"), ("smallthinker", "tree", "f32"),
          ("trinity", "tree", "bf16")]


def routing(shape, seed):
    """``(slot, valid)`` as ``routed_experts_apply`` makes them: every
    token's K distinct experts of E, the assignments to the H held ones
    numbered by expert (stable), the others out of range."""
    T, K, _, R, E, H = shape
    rng = np.random.default_rng(seed)
    idx = np.argsort(rng.random((T, E)), axis=1)[:, :K].reshape(-1)
    held = idx < H
    order = np.argsort(np.where(held, idx, H), kind="stable")
    slot = np.full(T * K, T * K, np.int32)
    n_held = int(held.sum())
    if n_held > R:
        raise ValueError(f"{n_held} held assignments for {R} rows")
    slot[order[:n_held]] = np.arange(n_held, dtype=np.int32)
    return slot.reshape(T, K), held.reshape(T, K)


def device_ms(calls):
    """{instruction: ms a call} of the newest trace's leaf operations."""
    from chipbench import trace_reduce

    ops = trace_reduce.load(TRACE_DIR)
    by_device = trace_reduce.leaf_ops(ops)
    out = {}
    for listed in by_device.values():
        for op in listed:
            out[op.name] = out.get(op.name, 0.0) \
                + (op.end - op.start) * 1e3 / calls / len(by_device)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def main():
    import jax
    import jax.numpy as jnp

    device = jax.devices()[0]
    if device.platform != "tpu" and not REHEARSE:
        sys.exit(f"needs the chip, found {device.platform}")
    known = forms()
    inputs, results, reference = {}, [], {}
    for shape_name, form, out_dtype in PROBES:
        shape = SHAPES[shape_name]
        T, K, M, R, _, _ = shape
        if shape_name not in inputs:
            slot, valid = routing(shape, 35)
            keys = jax.random.split(jax.random.key(35), 2)
            inputs[shape_name] = (
                jax.random.normal(keys[0], (R, M), jnp.bfloat16),
                jax.random.uniform(keys[1], (R, 1), jnp.float32),
                jnp.asarray(slot), jnp.asarray(valid))
        args = inputs[shape_name]
        dtype = jnp.bfloat16 if out_dtype == "bf16" else jnp.float32

        def way_back(out, w, slot, valid, fn=known[form], dtype=dtype):
            rows = (out * w).astype(jnp.bfloat16)     # the producer
            return fn(rows, slot, valid).astype(dtype)

        call = jax.jit(way_back)
        got = jax.block_until_ready(call(*args))
        for _ in range(2):
            jax.block_until_ready(call(*args))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        for _ in range(CALLS):
            got = call(*args)
        jax.block_until_ready(got)
        jax.profiler.stop_trace()
        ops = device_ms(CALLS) if device.platform == "tpu" else {}
        first = reference.setdefault((shape_name, out_dtype), got)
        producer = sum(ms for name, ms in ops.items()
                       if "multiply" in name.split(" = ")[0])
        # rows gathered: the padded forms read 8 slots a token
        k_read = K + (-K % 8 if form.startswith(("pad", "both")) else 0)
        record = {
            "shape": shape_name, "T,K,M,R": [T, K, M, R], "form": form,
            "out": out_dtype, "calls": CALLS,
            "table_mb": R * M * 2 / 1e6,
            "valid_share": float(np.mean(np.asarray(args[3]))),
            "ms_a_call": sum(ops.values()),
            "ms_producer": producer,
            "ms_way_back": sum(ops.values()) - producer,
            "gathered_mb": T * k_read * M * 2 / 1e6,
            "max_abs_diff_to_first_form": float(jnp.max(jnp.abs(
                got.astype(jnp.float32) - first.astype(jnp.float32)))),
            "ops": ops, "device": device.device_kind,
        }
        results.append(record)
        print(f"{shape_name:13s} {form:7s} {out_dtype:4s} "
              f"{record['ms_way_back']:7.3f} ms the way back "
              f"(+ producer {producer:.3f}), "
              f"{record['ms_way_back'] * 1e6 / max(record['gathered_mb'], 1e-9) / 1e3:.2f}"
              f" ns a gathered KB; diff {record['max_abs_diff_to_first_form']}",
              flush=True)
        for name, ms in list(ops.items())[:8]:
            print(f"        {ms:7.3f}  {name}")
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
