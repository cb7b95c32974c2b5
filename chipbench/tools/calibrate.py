#!/usr/bin/env python3
"""Read the numbers a cell's limits are set from, on the chip, in one
process: for each seed the program's first steps against the float32
reference (the sound readings), and for the first ``--control`` seeds
the reference computed in the control's precision against itself (the
readings the limit has to stay under).

    python3 chipbench/tools/calibrate.py --workload <cell> \\
        --seeds 101,102,... --control 3 [--rehearse 1]

Prints one JSON line a seed, then the largest sound and the smallest
control reading of every number compared.  The benchmark's own runs
never run this.

A cell of several ranks is read on one chip (several seeds' states of
several ranks in one process hung the machine once): ``--control-only
1`` reads the control, which needs no program, and ``--one-chip 1`` the
sound readings from a stand-in for the ranks' step: the program's own
loss function and optimizer, the ranks' rows one rank after another,
their gradients averaged as the step's allreduce averages them, as many
steps as the cell follows.  Read a few of its seeds in the cell's own
runs too.  Where the numbers compared are well-conditioned the two
agree number for number (1e-7 on the CPU and for Mistral's four-chip
cell on the chip); ResNet's do not (a third loss of 0.0008 against
0.0143 on one seed): its first steps turn one rounding into another
draw of every number (``tools/leaf_look.py``;
``limits/resnet50-b128-dp4.json``, ``the_look``), so there the two are
readings of one distribution and a limit is set from the largest of
both.
"""

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def ranks_on_one_chip(c, key, batch):
    """What ``Cell.first_steps`` finds, for a cell of several ranks
    whose adapter has ``loss_fn`` and ``optimizer``, without the ranks:
    each rank's loss and gradient from the program's loss function on
    its own rows (with the model's state where the adapter has one:
    BatchNorm's statistics, a rank's own in the step and averaged over
    the ranks after it, as the compiled step does), the means of both
    over the ranks, the program's optimizer over ``check_steps`` steps,
    and the loss after them where the mix asks for it."""
    import jax
    import optax

    from chipbench import weights

    n = c.ranks
    loss_fn = c.adapter.loss_fn(c.config, c.workload, c.rehearse)
    optimizer = c.adapter.optimizer(c.workload)
    has_aux = c.aux_spec is not None
    shards = [jax.tree.map(
        lambda a: a.reshape((n, -1) + a.shape[1:])[rank], batch)
        for rank in range(n)]

    def loss_of(params, aux, shard):
        """(loss, the model's new state or None)"""
        if has_aux:
            return loss_fn(params, aux, shard)
        return loss_fn(params, shard), None

    @functools.partial(jax.jit, donate_argnums=0)
    def add(total, params, aux, shard):
        (loss, new_aux), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params, aux, shard)
        return jax.tree.map(lambda t, x: t + x / n, total,
                            {"loss": loss, "grads": grads, "aux": new_aux})

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(params, opt_state, grads):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, \
            weights.leaf_norms(grads)

    params, aux = c.make_weights(key)
    losses, grad_norms, opt_state = [], None, None
    for i in range(c.workload["check_steps"]):
        total = jax.tree.map(jax.numpy.zeros_like,
                             {"loss": 0.0, "grads": params, "aux": aux})
        for shard in shards:
            total = add(total, params, aux, shard)
        losses.append(float(total["loss"]))
        aux = total["aux"]
        if opt_state is None:       # not held beside the first gradients
            opt_state = jax.jit(optimizer.init)(params)
        params, opt_state, norms = update(params, opt_state,
                                          total.pop("grads"))
        if i == 0:
            grad_norms = jax.device_get(norms)
    del opt_state, total
    delta_norms = jax.jit(lambda p, k: weights.leaf_norms(jax.tree.map(
        lambda a, b: a - b, p, weights.make(k, c.spec))))(params, key)
    if c.workload.get("check_loss_after"):
        loss_only = jax.jit(lambda *args: loss_of(*args)[0])
        losses.append(sum(float(loss_only(params, aux, shard))
                          for shard in shards) / n)
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": jax.device_get(delta_norms)}


def main():
    from chipbench import run as harness

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control", type=int, default=3,
                        help="read the control too on this many seeds, "
                             "the first")
    parser.add_argument("--rehearse", type=int, default=0)
    parser.add_argument("--control-only", type=int, default=0,
                        help="1: only the control against the reference, "
                             "which takes one chip whatever the cell has")
    parser.add_argument("--one-chip", type=int, default=0,
                        help="1: a cell of several ranks on one chip, by "
                             "ranks_on_one_chip")
    args = parser.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    one_chip = args.control_only or args.one_chip
    c = harness.Cell(args.workload, args.rehearse, need_chips=not one_chip)

    import jax

    from chipbench import weights

    rows = []

    def numbers(found, ref):
        return {name: value
                for name, value, _, _ in harness.gaps(found, ref, c.limits)}

    def read(seed, with_control, program):
        key = weights.seed_key(seed)
        batch = c.make_batch(key)
        ref = c.follow_reference(key, batch)
        row = {"seed": seed}
        if with_control:
            row["control"] = numbers(
                c.follow_reference(key, batch, c.control), ref)
        if program:
            found = program(key, batch)
            if found:
                row["sound"] = numbers(found, ref)
                row["losses"] = {"program": found["losses"],
                                 "reference": ref["losses"]}
        rows.append(row)
        print(json.dumps(row), flush=True)

    if one_chip:
        for i, seed in enumerate(seeds):
            read(seed, args.control_only or i < args.control,
                 None if args.control_only
                 else functools.partial(ranks_on_one_chip, c))
    else:
        shared = harness.Shared(c.ranks)

        def drive(rank, n_ranks):
            lead = rank == 0

            def program(key, batch):
                step, state, staged = c.start(key, batch, rank, lead)
                return c.first_steps(step, state, staged, key, lead,
                                     shared)[1]

            for i, seed in enumerate(seeds):
                if lead:
                    read(seed, i < args.control, program)
                else:
                    key = weights.seed_key(seed)
                    program(key, c.make_batch(key))
                shared.sync()

        c.adapter.launch(c.workload, shared.guarded(drive))

    summary = {}
    for name in rows[0].get("sound") or rows[0]["control"]:
        sound = [r["sound"][name] for r in rows if "sound" in r]
        controls = [r["control"][name] for r in rows if "control" in r]
        summary[name] = {
            "sound_largest": max(sound) if sound else None,
            "control_smallest": min(controls) if controls else None}
    print(json.dumps({"summary": summary, "seeds": len(rows),
                      "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main()
