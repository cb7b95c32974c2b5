"""Sharded training steps: the SPMD counterpart of the reference's
``DistributedOptimizer`` wrap (``horovod/torch/optimizer.py:516``,
``horovod/tensorflow/__init__.py:889``).

Where the reference intercepts per-parameter gradients and issues NCCL
allreduces from hooks, the TPU-native path compiles the *entire*
training step — forward, backward, optimizer update — as one
``jax.jit`` program over a mesh.  Gradient reduction is not an op we
issue; it is the transfer XLA inserts because parameters are
replicated (or fsdp-sharded) while the batch is split.  That single
design move eliminates the reference's negotiation/fusion machinery
from the hot path (SURVEY §2.8: "fusion → XLA already fuses").
"""

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.transformer import (
    TransformerConfig, TransformerLM, lm_loss, make_fused_lm_loss,
)
from ._shard_map import make_flash_attention_fn
from .mesh import BATCH_AXES
from .ring_attention import make_ring_attention_fn
from .sharding import (
    batch_sharding, transformer_param_shardings, replicated,
)


def make_lm_train_step(mesh: Mesh, cfg: TransformerConfig,
                       optimizer=None, *, sequence_parallel: bool = False,
                       attention_impl: str = "ring",
                       learning_rate: float = 1e-3,
                       fused_ce: bool = False,
                       ce_chunks: int = 16,
                       pipeline=None,
                       sharded=None):
    """Build (init_fn, step_fn) for the transformer over ``mesh``.

    ``step_fn(state, tokens) -> (state, loss)`` is jitted with explicit
    in/out shardings: params follow the tp/fsdp/ep/pp rules
    (sharding.py), the batch is split over dp+fsdp, and the sequence
    over sp when ``sequence_parallel`` — via ring attention
    (``attention_impl="ring"``, S/n memory, n ppermute hops) or
    Ulysses all-to-all head/sequence exchange (``"ulysses"``, two
    fused all_to_alls, needs (n_heads / tp) % sp == 0).

    ``fused_ce=True`` fuses the logits projection into a
    sequence-chunked cross-entropy (``ce_chunks`` chunks) so the
    (B, S, V) logits tensor never hits HBM; each chunk's gradient is
    formed with its logits, in the forward pass (``models.
    chunked_lm_loss``: reverse-mode first derivatives only).

    ``pipeline`` opts the step into the MPMD pipeline runtime
    (runtime.py; docs/parallelism.md): a :class:`~.runtime.
    PipelineSpec` (or dict / bare stage count) whose ``pp`` must match
    ``mesh``'s pp axis.  The decoder stack runs as explicit 1F1B /
    interleaved / GPipe instruction streams over per-stage sub-meshes
    while dp/tp/sp collectives still compile into the per-stage chunk
    programs — the dp×tp×pp path.  Same return contract; the step is
    not one fused program (that is the point — the schedule is
    runtime data the autotuner flips between steps).
    """
    optimizer = optimizer or optax.adamw(learning_rate)
    if sharded is None:
        from ..common import env as env_mod
        sharded = env_mod.get_bool(env_mod.HOROVOD_SHARDED_OPTIMIZER)
    if attention_impl not in ("ring", "ulysses", "flash"):
        raise ValueError(
            f"attention_impl must be 'ring', 'ulysses', or 'flash', "
            f"got {attention_impl!r}")
    if pipeline is not None:
        from .runtime import PipelineSpec, make_mpmd_lm_train_step

        if isinstance(pipeline, int):
            pipeline = PipelineSpec(pp=pipeline)
        elif isinstance(pipeline, dict):
            pipeline = PipelineSpec(**pipeline)
        if pipeline.pp > 1:
            if fused_ce:
                raise ValueError(
                    "fused_ce is not available under the MPMD "
                    "pipeline runtime: the loss head lives inside the "
                    "last stage's value_and_grad chunk program")
            att_factory = None
            if sequence_parallel:
                if attention_impl == "flash":
                    raise ValueError(
                        "attention_impl='flash' is the single-shard "
                        "pallas kernel; with sequence_parallel use "
                        "'ring' or 'ulysses'")
                att_factory = make_ring_attention_fn \
                    if attention_impl == "ring" else None
                if att_factory is None:
                    from .ulysses import make_ulysses_attention_fn
                    att_factory = make_ulysses_attention_fn
            elif attention_impl == "flash":
                att_factory = make_flash_attention_fn
            return make_mpmd_lm_train_step(
                mesh, cfg, pipeline, optimizer,
                attention_fn_factory=att_factory)
    if not sequence_parallel and attention_impl not in ("ring", "flash"):
        raise ValueError(
            "attention_impl='ulysses' only takes effect with "
            "sequence_parallel=True — set it, or drop attention_impl")
    attention_fn = None
    if sequence_parallel:
        if attention_impl == "flash":
            raise ValueError(
                "attention_impl='flash' is the single-shard pallas "
                "kernel; with sequence_parallel use 'ring' (itself "
                "flash-style streaming) or 'ulysses'")
        if attention_impl == "ring":
            attention_fn = make_ring_attention_fn(mesh)
        else:
            from .ulysses import make_ulysses_attention_fn
            attention_fn = make_ulysses_attention_fn(mesh)
        model = TransformerLM(cfg, attention_fn=attention_fn)
    elif attention_impl == "flash":
        # pallas flash kernel on the MXU (ops/pallas_kernels.py):
        # O(S) memory instead of the S^2 score matrix
        model = TransformerLM(
            cfg, attention_fn=make_flash_attention_fn(mesh))
    else:
        model = TransformerLM(cfg)

    tok_sharding = batch_sharding(mesh, seq_sharded=sequence_parallel)

    # Attention carries no parameters, so init MUST be identical
    # across attention implementations — same rng, same weights,
    # whether the step later runs dense, flash, ring, or ulysses.
    # Initializing through `model` would break that on jax/flax
    # versions where a shard_map inside the scanned block perturbs the
    # traced rng derivation; the dense twin sidesteps it (and skips
    # interpret-mode pallas kernels during init).
    init_model = TransformerLM(cfg)

    def init(rng, sample_tokens):
        params = init_model.init(rng, sample_tokens)["params"]
        opt_state = optimizer.init(params)
        return {"params": params, "opt_state": opt_state,
                "step": jnp.zeros((), jnp.int32)}

    if fused_ce:
        # logits projection fused into a sequence-chunked loss
        # (models/transformer.py chunked_lm_loss): the (B, S, V) f32
        # logits tensor is never materialized
        loss_fn = make_fused_lm_loss(model, n_chunks=ce_chunks)
    else:
        def loss_fn(params, tokens):
            logits = model.apply({"params": params}, tokens)
            # next-token prediction: shift targets left
            return lm_loss(logits[:, :-1], tokens[:, 1:])

    def step(state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], tokens)
        updates, opt_state = optimizer.update(
            grads, state["opt_state"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        return {"params": params, "opt_state": opt_state,
                "step": state["step"] + 1}, loss

    def shard_state(state):
        pspec = transformer_param_shardings(mesh, state["params"])
        ospec = _opt_state_shardings(mesh, state["opt_state"],
                                     state["params"], pspec,
                                     sharded=sharded)
        return {"params": pspec, "opt_state": ospec,
                "step": replicated(mesh)}

    def jit_step(state):
        """Returns (compiled_step, state placed onto the mesh)."""
        spec = shard_state(state)
        compiled = jax.jit(
            step,
            in_shardings=(spec, tok_sharding),
            out_shardings=(spec, replicated(mesh)),
            donate_argnums=(0,))
        placed = jax.device_put(state, spec)
        if sharded:
            _record_opt_state_bytes(placed["opt_state"])
        return compiled, placed

    return init, step, jit_step, tok_sharding


def _record_opt_state_bytes(opt_state):
    """Export the ÷dp evidence for the SPMD path: per-device bytes of
    the placed optimizer state (scope="shard") next to the global
    bytes a dense replica would hold (scope="full")."""
    try:
        from .. import telemetry
        shard = full = 0
        for leaf in jax.tree_util.tree_leaves(opt_state):
            if not hasattr(leaf, "addressable_shards"):
                continue
            full += int(leaf.size) * leaf.dtype.itemsize
            shards = leaf.addressable_shards
            if shards:
                d = shards[0].data
                shard += int(np.prod(d.shape, dtype=np.int64)
                             if d.shape else 1) * leaf.dtype.itemsize
        telemetry.set_optimizer_state_bytes("shard", shard)
        telemetry.set_optimizer_state_bytes("full", full)
    except Exception:  # noqa: BLE001 — telemetry must never kill a
        pass           # training job


def _opt_state_shardings(mesh, opt_state, params, param_shardings,
                         sharded=False):
    """Optimizer-state sharding: any leaf whose shape matches a
    parameter's gets that parameter's sharding (adam m/v mirror the
    weights — sharding them alike keeps fsdp memory O(params/n));
    everything else (counts, scalars) is replicated.

    ``sharded=True`` is weight-update sharding for the SPMD path
    (arXiv:1909.09756; docs/parallelism.md): moment leaves are
    additionally split over the dp axes on their largest divisible
    axis.  With the optimizer state dp-sharded while params stay
    replicated, XLA's SPMD partitioner emits exactly the
    reducescatter(grads) → 1/dp-shard update → allgather(params)
    decomposition — the compiler-native spelling of the same
    mechanism the engine-path ``DistributedOptimizer(sharded=True)``
    runs by hand — and optimizer-state memory drops by dp."""
    flat_params = jax.tree_util.tree_leaves(params)
    flat_shard = jax.tree_util.tree_leaves(
        param_shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    by_shape = {}
    for p, s in zip(flat_params, flat_shard):
        by_shape.setdefault(p.shape, s)
    dp_axes = [a for a in BATCH_AXES if a in mesh.shape]
    dp_total = int(np.prod([mesh.shape[a] for a in dp_axes])) \
        if dp_axes else 1

    def dp_shard(shape, base):
        """Split the largest axis not already sharded by ``base``
        over the dp axes the base spec does not already use; fall
        back to ``base`` when nothing divides."""
        spec = list(base.spec) + [None] * (len(shape) - len(base.spec))
        used = set()
        for entry in spec:
            for a in (entry if isinstance(entry, tuple)
                      else (entry,) if entry else ()):
                used.add(a)
        free = [a for a in dp_axes if a not in used]
        total = int(np.prod([mesh.shape[a] for a in free])) \
            if free else 1
        if total <= 1:
            return base
        order = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in order:
            if not shape[i]:
                continue
            entry = spec[i]
            cur = (entry if isinstance(entry, tuple)
                   else (entry,) if entry else ())
            # an axis nominally sharded by size-1 mesh axes (tp/fsdp
            # on a pure-dp mesh) still has its full capacity free —
            # append the dp axes to the entry instead of skipping it
            factor = int(np.prod([mesh.shape[a] for a in cur])) \
                if cur else 1
            if (shape[i] // factor) % total == 0 \
                    and shape[i] // factor > 0:
                spec[i] = cur + tuple(free)
                return NamedSharding(mesh, P(*spec))
        return base

    def pick(leaf):
        if hasattr(leaf, "shape") and leaf.shape in by_shape \
                and len(leaf.shape) > 0:
            base = by_shape[leaf.shape]
            if sharded and dp_total > 1:
                return dp_shard(leaf.shape, base)
            return base
        return replicated(mesh)

    return jax.tree_util.tree_map(pick, opt_state)


def make_pipelined_lm_train_step(mesh: Mesh, cfg: TransformerConfig,
                                 n_microbatches: int, optimizer=None, *,
                                 learning_rate: float = 1e-3,
                                 fused_ce: bool = False,
                                 ce_chunks: int = 16):
    """Trainable GPipe: the decoder stack runs as a ``pp``-axis
    pipeline (pipeline.py gpipe — a differentiable scan of ppermute
    ticks) and the whole fwd/bwd/update compiles as one program.

    Returns (init, step, jit_step, tok_sharding) with the same contract
    as :func:`make_lm_train_step`, so callers can switch between the
    scan-over-sharded-layers path and the explicit pipeline path."""
    from .pipeline import make_pipelined_lm_apply

    optimizer = optimizer or optax.adamw(learning_rate)
    model = TransformerLM(cfg)
    pipe_apply = make_pipelined_lm_apply(mesh, cfg, n_microbatches)
    tok_sharding = batch_sharding(mesh)

    def init(rng, sample_tokens):
        params = model.init(rng, sample_tokens)["params"]
        opt_state = optimizer.init(params)
        return {"params": params, "opt_state": opt_state,
                "step": jnp.zeros((), jnp.int32)}

    if fused_ce:
        loss_fn = make_fused_lm_loss(pipe_apply, n_chunks=ce_chunks)
    else:
        def loss_fn(params, tokens):
            logits = pipe_apply({"params": params}, tokens)
            return lm_loss(logits[:, :-1], tokens[:, 1:])

    def step(state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], tokens)
        updates, opt_state = optimizer.update(
            grads, state["opt_state"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        return {"params": params, "opt_state": opt_state,
                "step": state["step"] + 1}, loss

    def jit_step(state):
        pspec = transformer_param_shardings(mesh, state["params"])
        ospec = _opt_state_shardings(mesh, state["opt_state"],
                                     state["params"], pspec)
        spec = {"params": pspec, "opt_state": ospec,
                "step": replicated(mesh)}
        compiled = jax.jit(
            step,
            in_shardings=(spec, tok_sharding),
            out_shardings=(spec, replicated(mesh)),
            donate_argnums=(0,))
        return compiled, jax.device_put(state, spec)

    return init, step, jit_step, tok_sharding


# ---------------------------------------------------------------------------
# Data-parallel step for arbitrary flax models (ResNet bench path)

def make_dp_train_step(mesh: Mesh, apply_fn: Callable, optimizer,
                       loss_fn: Callable):
    """Pure-DP training step for a replicated flax model: params
    replicated, batch split over dp+fsdp — byte-for-byte the
    reference's semantics (grad-allreduce-average) with the allreduce
    compiled in."""
    batch_shd = NamedSharding(mesh, P(BATCH_AXES))
    rep = replicated(mesh)

    def step(state, batch, labels):
        def objective(params):
            out = apply_fn({"params": params,
                            **state.get("extra", {})}, batch)
            return loss_fn(out, labels)
        loss, grads = jax.value_and_grad(objective)(state["params"])
        updates, opt_state = optimizer.update(
            grads, state["opt_state"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        new_state = dict(state)
        new_state.update(params=params, opt_state=opt_state,
                         step=state["step"] + 1)
        return new_state, loss

    def jit_step(state):
        """Returns (compiled_step, state placed onto the mesh)."""
        spec = jax.tree_util.tree_map(
            lambda _: rep, state,
            is_leaf=lambda x: hasattr(x, "shape") or np.isscalar(x))
        compiled = jax.jit(step,
                           in_shardings=(spec, batch_shd, batch_shd),
                           out_shardings=(spec, rep),
                           donate_argnums=(0,))
        return compiled, jax.device_put(state, spec)

    return step, jit_step
