"""What every adapter of a training step shares, whatever the model:
the program's runtime around the ranks (``hvd.init`` / ``hvd.run``) and
the look at the replicated state across chips.  Not an adapter: no
configuration names it."""


def launch(workload, fn):
    """Run ``fn(rank, n_ranks)`` on every rank inside an initialised
    runtime; the list of what each returned."""
    import horovod_tpu as hvd

    n = workload["ranks"]
    if n == 1:
        hvd.init()
        try:
            return [fn(0, 1)]
        finally:
            hvd.shutdown()
    return hvd.run(lambda: fn(hvd.rank(), n), np=n)


def replicas_agree(state):
    """How many leaves of the replicated parameters differ between the
    chips that hold them: each chip sums its own copy's bits, and the
    sums are compared.  A step that left out the exchange between chips
    leaves copies that differ."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.common.shard_compat import shard_map

    leaves = jax.tree.leaves(state["params"])
    mesh = leaves[0].sharding.mesh

    def checksum(*copies):
        return [jnp.sum(jax.lax.bitcast_convert_type(c, jnp.uint32),
                        dtype=jnp.uint32)[None] for c in copies]

    sums = jax.jit(shard_map(
        checksum, mesh=mesh, in_specs=tuple(P() for _ in leaves),
        out_specs=[P(mesh.axis_names[0]) for _ in leaves],
        check_vma=False))(*leaves)
    return sum(int(len(set(jax.device_get(s).tolist())) > 1) for s in sums)
