"""A benchmark cell's step program for a DESCRIBED v5e, with no chip:
the hash of its StableHLO (to show that a change leaves a cell's program
alone: run from both trees at ONE path and compare), and with
``--compile`` the chip's compiler's own program, where every gather
shows the memory space of its table and the strategy it took, and every
large float32 copy whether it changes the layout of what it copies.

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
over HBM (6 against 46 ns a row on the chip, PERF.md PR 35).

A ``copy`` whose result's layout (the ``{1,2,0`` of
``f32[1,2048,8192]{1,2,0:T(8,128)}``: minor to major) differs from its
operand's is a transpose through HBM.  Where a leaf of the state is
copied so on the way into the optimizer's fusion and back on the way
out, the gradient reached that fusion in another layout than the
state's: a ``transpose`` after the weight-gradient product, folded into
the product's result (PERF.md section 6, PR 49;
``models/dense.dense_product`` is the cure).  ``layout_copies`` lists
them, each with the leaf of the state it copies where its name, its
operand or the output it becomes says so.  Nothing runs here, so this
gives no times.
"""

import argparse
import collections
import hashlib
import importlib
import json
import math
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


TYPE = re.compile(r"(\w+)\[([\d,]*)\](?:\{([\d,]*))?")
STATE_LEAF = re.compile(r"state[\W_]+(params|opt_state[\W_]+0[\W_]+(mu|nu))[\W_]")


def _state_leaf(name):
    """``parameter`` / ``mu`` / ``nu`` for a leaf of the step's state by
    its name, as a parameter's instruction or an ``op_name`` spells it
    (``state['opt_state'][0].mu['embed']``,
    ``state__opt_state___0__mu__embed__.1``), else None."""
    match = STATE_LEAF.match(name)
    return match and (match.group(2) or "parameter")


def layout_copies(text, min_bytes=4_000_000):
    """[(instruction, result's type, operand's layout, what, bytes,
    fused)] of every ``copy`` of a compiled program's text whose result
    is float32, at least ``min_bytes`` large and laid out otherwise than
    its operand.  ``what`` is ``parameter``, ``mu``, ``nu`` or
    ``other``: the leaf of the state the copy's ``op_name`` or its
    operand names, or (a copy of a result, which carries no name) the
    leaf that the output it becomes is donated from.  ``fused`` is
    whether it sits inside a fusion's computation: a transposed read or
    write of that fusion, and no pass over HBM of its own."""
    types, parameters, outputs, copies = {}, {}, [], []
    entry = fused = False
    for line in text.splitlines():
        if line[:1] not in (" ", "}", ""):      # a computation's header
            entry = line.startswith("ENTRY ")
            fused = "fus" in line.split("(")[0]
        match = INSTRUCTION.match(line)
        if not match:
            continue
        name = match.group(1)
        types[name] = match.group(2)
        number = re.search(r" parameter\((\d+)\)", line)
        if entry and number:
            parameters[int(number.group(1))] = _state_leaf(name)
        if entry and line.lstrip().startswith("ROOT ") and " tuple(" in line:
            outputs = [operand.strip().lstrip("%") for operand in re.sub(
                r"/\*.*?\*/", "", line.split(" tuple(", 1)[1].split(")")[0]
            ).split(",")]
        operand = re.search(r" copy\(%?([\w.\-]+)\)", line)
        if operand:
            op_name = re.search(r'op_name="([^"]*)"', line)
            copies.append((name, operand.group(1), fused,
                           op_name.group(1).replace("\\", "")
                           if op_name else ""))
    donated = {int(out): int(number) for out, number in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}", text.split("\n", 1)[0])}
    found = []
    for name, operand, fused, op_name in copies:
        result = TYPE.match(types[name])
        source = TYPE.match(types.get(operand, ""))
        if not result or not source or result.group(1) != "f32" \
                or result.group(3) == source.group(3):
            continue
        size = 4 * math.prod(
            int(extent) for extent in result.group(2).split(",") if extent)
        if size < min_bytes:
            continue
        what = _state_leaf(op_name) or _state_leaf(operand)
        if not what and name in outputs:
            what = parameters.get(donated.get(outputs.index(name)))
        found.append((name, types[name].split(":")[0] + "}", source.group(3),
                      what or "other", size, fused))
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
        counts, sizes, shapes = (collections.Counter() for _ in range(3))
        for _, result, _, what, size, fused in layout_copies(text):
            for key in ("fused",) if fused else (what, "all"):
                counts[key] += 1
                sizes[key] += size
            shapes[result.split("{")[0] + (" fused" if fused else "")] += 1
        print("  float32 copies of 4 MB or more that change the layout,"
              " by what they copy:")
        for what in ("parameter", "mu", "nu", "other", "all", "fused"):
            print(f"    {what:9s} {counts[what]:4d} copies "
                  f"{sizes[what] / 1e9:7.2f} GB" + (
                "  (inside a fusion: no pass over HBM of their own, and"
                " in no row above)" if what == "fused" else ""))
        print("    shapes:", dict(shapes))
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)


if __name__ == "__main__":
    main()
