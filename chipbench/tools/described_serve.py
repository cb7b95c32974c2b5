"""A served cell's programs compiled for a DESCRIBED v5e, with no chip:
what the chip's compiler needs beside the weights and the pools for each
prompt bucket's prefill and ingest and each table bucket's decode, so
that a memory fault costs no chip time.  Nothing runs: no times.

    python chipbench/tools/described_serve.py <cell> [--all]   (cwd = a checkout)

Without ``--all`` only the widest bucket of each kind is compiled.
"""

import argparse
import importlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("cell")
    parser.add_argument("--all", action="store_true")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench.run import find_cell, load_json, with_rehearsal

    bench = load_json("BENCHMARK.json")
    cell, entry = find_cell(bench, args.cell)
    config = with_rehearsal(load_json(entry["file"]), False)
    workload = with_rehearsal(load_json(
        bench["paths"][0], "workloads", cell["traffic"] + ".json"), False)
    adapter = importlib.import_module(
        f"chipbench.adapters.{config['adapter']}")
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    programs = adapter.make_programs(config, workload)
    cfg = programs.cfg
    params = on_chip(jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, cfg.dtype),
        adapter.param_shapes(config, workload)))
    pool = shape(programs.pool_shape, cfg.dtype)
    held = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(params)) \
        + 2 * pool.size * pool.dtype.itemsize
    slots, bt = programs.max_slots, programs.block_tokens
    i32 = jnp.int32
    listed = []
    prompts = programs.prompt_buckets if args.all \
        else programs.prompt_buckets[-1:]
    tables = programs.table_buckets if args.all \
        else programs.table_buckets[-1:]
    for rows in prompts:
        kv = shape((cfg.n_layers, rows, cfg.kv_heads, cfg.head_dim),
                   cfg.dtype)
        listed.append((f"prefill {rows}", programs._prefill_program(rows),
                       (params, shape((1, rows), i32), shape((), i32))))
        listed.append((f"ingest {rows}", programs._ingest_program(rows),
                       (pool, pool, kv, kv, shape((-(-rows // bt),), i32),
                        shape((), i32))))
    for width in tables:
        listed.append((f"decode {width}", programs._decode_program(width),
                       (params, pool, pool, shape((slots, 1), i32),
                        shape((slots,), i32), shape((slots, width), i32),
                        shape((slots,), jnp.bool_))))
    with jax.enable_x64(False):
        for name, program, shapes in listed:
            compiled = program.lower(*shapes).compile()
            memory = compiled.memory_analysis()
            print(json.dumps({
                "program": name,
                "weights_and_pools_gb": held / 1e9,
                "temporaries_gb": memory.temp_size_in_bytes / 1e9,
                "arguments_gb": memory.argument_size_in_bytes / 1e9,
                "outputs_gb": memory.output_size_in_bytes / 1e9,
                "aliased_gb": memory.alias_size_in_bytes / 1e9}),
                flush=True)


if __name__ == "__main__":
    main()
