"""A benchmark cell's step program for a DESCRIBED v5e, with no chip:
the hash of its StableHLO (to show that a change leaves a cell's program
alone: run from both trees at ONE path and compare), and with
``--compile`` the chip's compiler's own program, where every gather
shows the memory space of its table and the strategy it took.

    python tools/described_step.py <cell> [--compile] [--out <file>]
                                                  (cwd = a checkout)

A four-chip cell's step is built as ``hvd.run`` builds it on the host
with four chips: ``MeshExecutor`` over the described 2x2, the state
replicated, the batch's leading axis (a rank's rows each) over ``hvd``.
Its compiled text names the collectives as the chip's executable does.

A gather of rows is a ``kind=kCustom`` fusion with ``gather`` in its
``op_name``.  Its first operand is the table: ``S(1)`` in that
operand's layout (``bf16[30720,1280]{1,0:T(8,128)(2,1)S(1)}``) is the
on-chip memory, no ``S(...)`` is HBM; the fusion's ``integer_config`` is
0 for the fast strategy over a table in ``S(1)`` and 128 for the one
over HBM (6 against 46 ns a row on the chip, PERF.md PR 35).  Nothing
runs here, so this gives no times.
"""

import argparse
import hashlib
import importlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def lowered_step(cell, **config_changes):
    sys.path.insert(0, os.getcwd())
    import jax
    from jax.experimental import topologies
    from jax.sharding import (NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from chipbench.run import with_rehearsal
    from horovod_tpu.ops import device_sums, pallas_kernels
    from horovod_tpu.ops.xla_ops import MeshExecutor

    def load(*parts):
        with open(os.path.join("chipbench", *parts)) as f:
            return with_rehearsal(json.load(f), False)

    with open("BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    config = dict(load("configs", entry["config"] + ".json"),
                  **config_changes)
    workload = load("workloads", entry["traffic"] + ".json")
    adapter = importlib.import_module(
        f"chipbench.adapters.{config['adapter']}")

    jax.config.update("jax_traceback_in_locations_limit", 0)
    pallas_kernels.default_interpret = lambda: False
    chips = list(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices)[:entry["chips"]]
    ex = MeshExecutor(chips, len(chips))
    step = adapter.make_step(config, workload, False)
    params, aux = adapter.param_shapes(config, workload)

    def state_of(p):
        state = {"params": p, "opt_state": step.optimizer.init(p)}
        if step.has_aux:
            state["aux"] = {} if aux is None else aux
        names = device_sums.declared(step.loss_fn)
        if names:
            state[device_sums.STATE_KEY] = device_sums.zeros(names)
        return state

    if len(chips) == 1:
        replicated = by_rank = SingleDeviceSharding(chips[0])
    else:
        replicated = NamedSharding(ex.mesh, P())
        by_rank = NamedSharding(ex.mesh, P("hvd"))

    def shaped(tree, sharding):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    make_input = importlib.import_module(
        f"chipbench.inputs.{workload['input']['kind']}").make
    ranks = len(chips)
    with jax.enable_x64(False):
        # every rank's rows, as the step stages them: a leading rank axis
        batch = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                (ranks, a.shape[0] // ranks) + a.shape[1:], a.dtype),
            jax.eval_shape(lambda key: make_input(
                key, config, workload, ranks * workload["batch"]),
                jax.random.key(0)))
        return step._build(ex).lower(
            shaped(jax.eval_shape(state_of, params), replicated),
            shaped(batch, by_rank))


INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ")


def gathers(text):
    """[(instruction, result, table's type with its memory space,
    integer_config, op_name)] of every row gather of a compiled
    program's text."""
    types, found = {}, []
    for line in text.splitlines():
        match = INSTRUCTION.match(line)
        if match:
            types[match.group(1)] = match.group(2)
    for line in text.splitlines():
        match = INSTRUCTION.match(line)
        if not match or "kind=kCustom" not in line \
                or "gather" not in line:
            continue
        operand = re.search(r" fusion\(%?([\w.\-]+)", line)
        strategy = re.search(r'"integer_config":\{"integer":"(\d+)"', line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        found.append((match.group(1), re.sub(r"\{.*", "", match.group(2)),
                      types.get(operand.group(1), "?") if operand else "?",
                      strategy.group(1) if strategy else None,
                      op_name.group(1) if op_name else ""))
    return found


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("cell")
    parser.add_argument("--compile", action="store_true")
    parser.add_argument("--out", help="write the program's text here")
    args = parser.parse_args()
    lowered = lowered_step(args.cell)
    text = lowered.as_text()
    print(args.cell, "stablehlo sha256",
          hashlib.sha256(text.encode()).hexdigest(), len(text), "bytes")
    if args.compile:
        compiled = lowered.compile()
        print(compiled.memory_analysis())
        text = compiled.as_text()
        counted = {}        # alike but for the layer and the instruction
        for _, result, table, strategy, op_name in gathers(text):
            scope = "/".join(op_name.split("/")[-2:])
            key = (result, table, strategy, scope)
            counted[key] = counted.get(key, 0) + 1
        for (result, table, strategy, scope), n in counted.items():
            print(f"  {n:3d} x gather {result:18s} table {table:44s} "
                  f"strategy {strategy:4s} {scope}")
        copies = [line.strip()[:110] for line in text.splitlines()
                  if re.search(r" = bf16\[\d+,\d+,\d+\]\S* reshape\(", line)]
        print("  reshapes that are instructions of their own:", copies)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)


if __name__ == "__main__":
    main()
