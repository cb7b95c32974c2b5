"""GPipe-style pipeline parallelism over the ``pp`` mesh axis.

Not in the reference (SURVEY §2.7: no PP engine; process sets are the
substrate users would build one on).  TPU-native formulation: stages
are shards of the scanned layer axis, activations hop stage-to-stage
with ``lax.ppermute`` (one ICI neighbour hop), and microbatches stream
through a ``lax.scan`` of ``n_micro + n_stages - 1`` ticks — the
classic collective-permute pipeline from the scaling playbook, written
as a ``shard_map`` block so it composes under an outer ``jax.jit``.

The transformer's decoder stack is already stacked on a leading layer
axis (``nn.scan`` in models/transformer.py), so a stage's parameters
are just the local shard of that axis — no repacking.
"""

from typing import Callable

import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ._shard_map import axis_size, shard_map


def gpipe(stage_fn: Callable, local_stage_params, microbatches,
          axis_name: str = "pp"):
    """Run ``microbatches`` (M, ...) through the pipeline.

    Must be called inside shard_map with ``axis_name`` bound.
    ``stage_fn(local_stage_params, x) -> x`` applies this device's
    stage.  Returns (M, ...) outputs, replicated across the axis.

    The tick loop is a ``lax.scan`` (not fori/while) so the whole
    pipeline is **reverse-mode differentiable**: scan transposes to a
    reverse scan, ``ppermute`` to the inverted permutation, and the
    last-stage psum to a broadcast — giving exact GPipe gradients with
    the usual O(M) activation memory (use ``jax.checkpoint`` around
    ``stage_fn`` to trade recompute for memory).
    """
    n = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    M = microbatches.shape[0]
    perm = [(j, (j + 1) % n) for j in range(n)]

    def tick(state, t):
        # stage 0 injects microbatch t while t < M; later stages use
        # the activation ppermuted in from the previous stage.
        inject = microbatches[jnp.minimum(t, M - 1)]
        state = jnp.where(my == 0, jnp.where(t < M, inject, state), state)
        state = stage_fn(local_stage_params, state)
        emit = state
        state = lax.ppermute(state, axis_name, perm)
        return state, emit

    state0 = jnp.zeros_like(microbatches[0])
    _, emitted = lax.scan(tick, state0, jnp.arange(M + n - 1))
    # microbatch m leaves the last stage at tick m + n - 1: its
    # emissions at ticks [n-1, M+n-1) are the pipeline outputs
    outputs = emitted[n - 1:]
    # replicate finished microbatches from the last stage to all stages
    return lax.psum(jnp.where(my == n - 1, outputs, 0.0), axis_name)


def make_pipelined_lm_apply(mesh, cfg, n_microbatches: int,
                            batch_axes=("dp", "fsdp")):
    """Build ``apply(params, tokens) -> logits`` running the decoder
    stack as a pipeline over ``pp`` (embed/unembed replicated).

    ``params`` is the standard TransformerLM params pytree; the
    ``layers`` subtree (leading axis = n_layers) is consumed sharded
    over ``pp``.
    """
    from ..models.transformer import (
        DecoderBlock, RMSNorm, rope_angles)
    import flax.linen as nn

    block = DecoderBlock(cfg)
    angles_full = jnp.asarray(
        rope_angles(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta))

    def stage_fn(local_layers, x, angles):
        def body(h, layer_params):
            h, _ = block.apply({"params": layer_params}, h, angles)
            return h, None
        x, _ = lax.scan(body, x, local_layers)
        return x

    def pipe_block(local_layers, x_emb, angles):
        # x_emb: (local_B, S, D) — batch already sharded by shard_map
        B = x_emb.shape[0]
        M = n_microbatches
        if B % M != 0:
            raise ValueError(f"local batch {B} not divisible by "
                             f"microbatches {M}")
        mbs = x_emb.reshape((M, B // M) + x_emb.shape[1:])
        outs = gpipe(lambda p, h: stage_fn(p, h, angles),
                     local_layers, mbs)
        return outs.reshape(x_emb.shape)

    mapped = shard_map(
        pipe_block, mesh=mesh,
        in_specs=(P("pp"), P(batch_axes, None, None), P()),
        out_specs=P(batch_axes, None, None),
        check_vma=False)

    def apply(params, tokens, pre_logits=False):
        p = params["params"] if "params" in params else params
        emb = p["embed"]
        x = emb[tokens].astype(cfg.dtype)
        angles = angles_full[: tokens.shape[1]]
        x = mapped(p["layers"], x, angles)
        x = RMSNorm(cfg.dtype, name="ln_final").apply(
            {"params": p["ln_final"]}, x)
        if pre_logits:
            # same contract as TransformerLM(pre_logits=True): the
            # caller fuses the projection into a chunked loss
            return x, emb
        # activation-dtype operands with f32 accumulation, matching
        # TransformerLM's unembed (a full-f32 matmul would run at a
        # fraction of the MXU's bf16 rate)
        return jnp.einsum("bsm,vm->bsv", x, emb.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)

    return apply
