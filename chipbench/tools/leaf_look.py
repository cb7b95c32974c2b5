#!/usr/bin/env python3
"""The look behind the limits on a CNN cell's leaf norms, on one chip in
one process: every leaf's norm, not the summaries ``calibrate.py``
prints, so that any way of comparing the tree can be read afterwards
from the file.

    python3 chipbench/tools/leaf_look.py --workload <cell> \\
        --seeds 201,202,... --deep 3 --out chiprun_out/leaf_look.jsonl

One JSON line a seed: the losses and ``{leaf: norm}`` of the first
gradient and of the parameters' change for ``reference`` (float32) and
``program`` (the cell's program by ``calibrate.ranks_on_one_chip``).
For the first ``--deep`` seeds also:

``control``: the reference in the cell's control precision.
``program_float32``: the program with float32 activations and every
product at ``highest``: the same algebra as the reference, so what it
reads is the two float32 computations' own rounding, and what the
program reads beyond it is its bfloat16.
``reference_moved`` / ``program_moved``: both again with every pixel of
the batch moved up by one bfloat16 unit in its last place.  A
reference that reads itself this far away says how far apart two
faithful computations of these numbers lie (their conditioning: no
program can be held closer); the program against itself says how far
apart two roundings of the same bfloat16 arithmetic lie, which is what
another compilation of it is (four chips' step beside one chip's).
``direction``: for every leaf ``|g - g_moved| / |g|`` of the
reference's first gradient: 1.41 where the two share nothing but the
norm.
``reference_whole_batch``: the reference with BatchNorm's statistics
over all the ranks' rows at once (one rank of ``ranks x batch`` rows):
what a synchronised BatchNorm would compute.
"""

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    from chipbench import run as harness
    from chipbench.tools import calibrate

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--deep", type=int, default=0)
    parser.add_argument("--rehearse", type=int, default=0)
    parser.add_argument("--out", required=True)
    args = parser.parse_args()
    c = harness.Cell(args.workload, args.rehearse, need_chips=False)

    import jax
    import jax.numpy as jnp

    from chipbench import weights

    def plain(found):
        return {"losses": found["losses"],
                "grad_norms": {k: float(v)
                               for k, v in found["grad_norms"].items()},
                "delta_norms": {k: float(v)
                                for k, v in found["delta_norms"].items()}}

    @contextlib.contextmanager
    def program_in_float32():
        model = c.adapter.model
        c.adapter.model = lambda config: model(config).clone(
            dtype=jnp.float32)
        try:
            with jax.default_matmul_precision("highest"):
                yield
        finally:
            c.adapter.model = model

    def first_gradient(key, batch):
        """The reference's first gradient as a tree: its step's momentum
        trace after one step from nought."""
        params = jax.jit(lambda k: weights.make(k, c.spec))(key)
        trace = jax.tree.map(jnp.zeros_like, params)
        return c.reference.step_program(c.config, c.workload)(
            params, trace, batch)[1]

    @jax.jit
    def apart(a, b):
        return {jax.tree_util.keystr(path): jnp.sqrt(jnp.sum(jnp.square(
            x - y))) / jnp.sqrt(jnp.sum(jnp.square(x)))
            for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                                    jax.tree.leaves(b))}

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as out:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            key = weights.seed_key(seed)
            batch = c.make_batch(key)
            row = {"seed": seed, "device": jax.devices()[0].device_kind,
                   "reference": plain(c.follow_reference(key, batch)),
                   "program": plain(
                       calibrate.ranks_on_one_chip(c, key, batch))}
            if i < args.deep:
                images, labels = batch
                moved = (jnp.nextafter(
                    images, jnp.array(jnp.inf, images.dtype)), labels)

                def in_float32():
                    with program_in_float32():
                        return calibrate.ranks_on_one_chip(c, key, batch)

                extras = {
                    "control": lambda: c.follow_reference(
                        key, batch, c.control),
                    "reference_moved": lambda: c.follow_reference(key, moved),
                    "program_moved": lambda: calibrate.ranks_on_one_chip(
                        c, key, moved),
                    "program_float32": in_float32}
                if c.ranks > 1:
                    extras["reference_whole_batch"] = \
                        lambda: c.reference.follow(
                            c.config, dict(c.workload, ranks=1), key, batch,
                            c.workload["check_steps"])
                for name, read in extras.items():
                    try:        # one that does not fit costs only itself
                        row[name] = plain(read())
                    except Exception as exc:
                        row[name + "_failed"] = repr(exc)[:500]
                row["direction"] = {k: float(v) for k, v in jax.device_get(
                    apart(first_gradient(key, batch),
                          first_gradient(key, moved))).items()}
            out.write(json.dumps(row) + "\n")
            out.flush()
            print(json.dumps({"seed": seed, "losses": {
                k: v["losses"] for k, v in row.items()
                if isinstance(v, dict) and "losses" in v}}), flush=True)


if __name__ == "__main__":
    main()
