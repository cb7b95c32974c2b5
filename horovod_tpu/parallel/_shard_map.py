"""Package-local re-export of shard_map + the attention wrappers."""

from functools import partial

from jax.sharding import PartitionSpec as P

from ..common.shard_compat import axis_size, shard_map  # noqa: F401


def make_attention_fn(kernel, mesh, *, batch_axes=("dp", "fsdp"),
                      seq_axis="sp", head_axis="tp"):
    """Wrap a per-shard attention kernel ``kernel(q, k, v, axis_name)``
    in shard_map so it drops into ``TransformerLM(attention_fn=...)``
    under an outer jit: q/k/v arrive (B, S, H, D), batch-sharded on
    ``batch_axes``, sequence-sharded on ``seq_axis``, head-sharded on
    ``head_axis``."""
    spec = P(batch_axes, seq_axis, head_axis, None)
    return shard_map(partial(kernel, axis_name=seq_axis), mesh=mesh,
                     in_specs=(spec, spec, spec), out_specs=spec,
                     check_vma=False)


def make_flash_attention_fn(mesh, *, batch_axes=("dp", "fsdp"),
                            head_axis="tp"):
    """The pallas flash kernel as a ``TransformerLM(attention_fn=...)``
    under a jit over ``mesh``.  The TPU compiler refuses to partition
    a Mosaic kernel on its own ("wrap the call in a shard_map"), so
    each device runs the kernel on its batch/head shard of the
    (B, S, H, D) operands; the sequence stays whole."""
    from ..ops.pallas_kernels import flash_attention

    spec = P(batch_axes, None, head_axis, None)

    def attention(q, k, v, window=None):
        return shard_map(partial(flash_attention, window=window),
                         mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)

    return attention
