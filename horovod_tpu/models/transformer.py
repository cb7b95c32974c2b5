"""Decoder-only Transformer LM, laid out for TPU parallelism.

The reference ships no model zoo of its own — its flagship workloads
are the synthetic benchmarks plus user models wrapped by
``DistributedOptimizer`` (``examples/pytorch/pytorch_synthetic_benchmark.py``,
``docs/benchmarks.rst``).  This model is the framework's long-context /
multi-chip flagship: every parallelism axis the ``parallel`` package
implements (dp / fsdp / tp / sp / ep / pp) maps onto it.

TPU-first choices:

* Pre-RMSNorm + SwiGLU + rotary position embeddings: all FLOPs live in
  large einsums that tile onto the MXU; bf16 activations, f32 params.
* Decoder blocks are stacked with ``nn.scan`` — one compiled block body
  scanned over a leading ``layers`` parameter axis.  This keeps compile
  time O(1) in depth and gives pipeline parallelism a natural stage
  axis (parallel/pipeline.py scans stages the same way).
* The attention inner function is pluggable: the sequence-parallel path
  substitutes ring attention (parallel/ring_attention.py) without
  touching the module.
* Optional mixture-of-experts MLP with dense one-hot dispatch: the
  expert einsum keeps a leading ``experts`` axis that the ``ep`` mesh
  axis shards; XLA inserts the token all_to_all.
"""

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import grad_hook


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    d_ff: int = 1408          # SwiGLU hidden; ~8/3 * d_model rounded to 128
    max_seq_len: int = 2048
    num_experts: int = 0      # 0 => dense MLP
    expert_top_k: int = 2
    moe_capacity_factor: float = 0.0   # > 0 => fixed-capacity routing
    # (parallel/moe.py): capacity = ceil(cf * tokens * topk / E),
    # deterministic drop/pad, O(topk) expert FLOPs per token and the
    # equal-splits slot layout the quantized alltoall wire exchanges;
    # 0 keeps the legacy dense one-hot dispatch (every expert sees
    # every token — O(E) FLOPs, no drops, no wire)
    n_kv_heads: Optional[int] = None   # GQA/MQA: kv heads < n_heads
    # (None => n_heads, i.e. standard multi-head attention); each kv
    # head serves n_heads/n_kv_heads query heads and the decode cache
    # shrinks by the same factor (llama-2/3 style)
    attention_window: Optional[int] = None   # sliding-window span
    # (mistral style): each query sees the last W positions only.
    # Applies consistently to training (dense or flash attention_fn)
    # AND the KV-cache decode path; ring/ulysses sequence-parallel
    # inners don't support it (rejected loudly)
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    remat: bool = False       # jax.checkpoint each block (HBM <-> FLOPs)
    remat_policy: str = "full"  # "full" recomputes everything;
    # "dots" saves matmul outputs (jax dots_with_no_batch_dims_saveable)
    # so the backward pass skips re-running the MXU work — worth ~400MB
    # * n_layers of HBM at (B=8, S=2048, d=1024) in exchange for the
    # ~33% remat recompute FLOPs; "dots_flash" additionally saves the
    # flash-attention kernel outputs (out + lse, checkpoint-named) so
    # the backward replay skips the pallas forward too

    @property
    def head_dim(self):
        return self.d_model // self.n_heads

    @property
    def kv_heads(self):
        kv = self.n_kv_heads if self.n_kv_heads is not None \
            else self.n_heads
        if kv < 1 or self.n_heads % kv:
            raise ValueError(
                f"n_kv_heads ({kv}) must divide n_heads "
                f"({self.n_heads})")
        return kv


def rope_angles(head_dim: int, max_seq: int, theta: float) -> np.ndarray:
    """Precomputed rotary angles (max_seq, head_dim // 2), float32."""
    inv_freq = 1.0 / theta ** (np.arange(0, head_dim, 2) / head_dim)
    pos = np.arange(max_seq)
    return np.einsum("s,f->sf", pos, inv_freq).astype(np.float32)


def apply_rope(x, angles):
    """x: (B, S, H, D); angles: (S, D//2) — rotate pairs of channels."""
    sin = jnp.sin(angles)[None, :, None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def grouped_causal_attention(q, k, v, *, offset=0, window=None):
    """GQA attention against an UN-expanded kv tensor: q (B, T, H, D)
    with H = KV*G query heads attends k/v (B, S, KV, D) directly —
    no (B, S, H, D) materialization, so the decode path reads the
    reduced cache at its stored size (the GQA bandwidth win).
    ``window`` restricts each query to the last ``window`` positions
    (sliding-window attention)."""
    B, T, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, T, KV, G, D)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(D)
    q_pos = jnp.arange(T)[:, None] + offset
    k_pos = jnp.arange(S)[None, :]
    mask = q_pos >= k_pos
    if window is not None:
        mask = mask & (q_pos - k_pos < window)
    mask = mask[None, None, None]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return o.reshape(B, T, H, D)


def dense_causal_attention(q, k, v, *, offset=0, window=None):
    """Reference attention inner: (B, S, H, D) -> (B, S, H, D) with a
    causal mask.  ``offset`` shifts query positions (used when the
    sequence axis is sharded and this shard holds positions
    [offset, offset + S)).  ``window`` limits each query to the last
    ``window`` positions (sliding-window attention; None = full
    causal)."""
    depth = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(depth)
    q_pos = jnp.arange(q.shape[1])[:, None] + offset
    k_pos = jnp.arange(k.shape[1])[None, :]
    mask = q_pos >= k_pos
    if window is not None:
        mask = mask & (q_pos - k_pos < window)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class RMSNorm(nn.Module):
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1,
                                         keepdims=True) + 1e-6)
        return (y * scale).astype(self.dtype)


class Attention(nn.Module):
    cfg: TransformerConfig
    attention_fn: Callable = dense_causal_attention
    decode: bool = False      # KV-cache autoregressive path

    @nn.compact
    def __call__(self, x, angles, offset=0):
        cfg = self.cfg
        H, D = cfg.n_heads, cfg.head_dim
        KV = cfg.kv_heads          # == H unless GQA/MQA configured
        dense = lambda feats, name: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=False, dtype=cfg.dtype,
            param_dtype=jnp.float32, name=name)
        q = dense((H, D), "wq")(x)
        k = dense((KV, D), "wk")(x)
        v = dense((KV, D), "wv")(x)
        q = apply_rope(q, angles)
        k = apply_rope(k, angles)

        def expand_kv(t):
            # training path only: each kv head serves H/KV query
            # heads; materializing the repeat keeps every attention
            # inner fn (dense/flash/ring/ulysses) unchanged and costs
            # exactly what MHA's k/v already cost.  The decode path
            # below never expands — grouped_causal_attention reads
            # the reduced cache at its stored size.
            if KV == H:
                return t
            return jnp.repeat(t, H // KV, axis=2)

        if self.decode:
            if self.attention_fn is not dense_causal_attention:
                # ring/ulysses/flash are training inner fns with their
                # own sharding contracts; silently decoding dense would
                # break them — fail loudly
                raise ValueError(
                    "KV-cache decoding supports the dense attention "
                    "path only; build the model with the default "
                    "attention_fn for generation")
            # KV cache: write this chunk at [offset, offset+T) and
            # attend over the full cache — rows past the write head are
            # zeros and masked away by causality (offset may be traced).
            # The cache stores KV heads (H/KV x smaller under GQA) and
            # expands after the update.
            B = x.shape[0]
            ck = self.variable(
                "cache", "k", jnp.zeros,
                (B, cfg.max_seq_len, KV, D), cfg.dtype)
            cv = self.variable(
                "cache", "v", jnp.zeros,
                (B, cfg.max_seq_len, KV, D), cfg.dtype)
            ck.value = jax.lax.dynamic_update_slice_in_dim(
                ck.value, k.astype(ck.value.dtype), offset, axis=1)
            cv.value = jax.lax.dynamic_update_slice_in_dim(
                cv.value, v.astype(cv.value.dtype), offset, axis=1)
            if KV == H:
                o = dense_causal_attention(
                    q, ck.value, cv.value, offset=offset,
                    window=cfg.attention_window)
            else:
                o = grouped_causal_attention(
                    q, ck.value, cv.value, offset=offset,
                    window=cfg.attention_window)
        else:
            if cfg.attention_window is not None:
                # config-driven sliding window: forwarded to inners
                # that accept it (dense reference, pallas flash); the
                # sequence-parallel inners (ring/ulysses) don't — a
                # silent full-causal fallback would train a different
                # model than the config says, so fail loudly
                try:
                    o = self.attention_fn(
                        q, expand_kv(k), expand_kv(v),
                        window=cfg.attention_window)
                except TypeError as exc:
                    raise ValueError(
                        f"attention_window={cfg.attention_window} "
                        f"set but attention_fn "
                        f"{getattr(self.attention_fn, '__name__', self.attention_fn)!r} "
                        f"does not accept a window= kwarg (ring/"
                        f"ulysses sequence parallelism does not "
                        f"support sliding windows)") from exc
            else:
                o = self.attention_fn(q, expand_kv(k), expand_kv(v))
        return nn.DenseGeneral(cfg.d_model, axis=(-2, -1), use_bias=False,
                               dtype=cfg.dtype, param_dtype=jnp.float32,
                               name="wo")(o)


class SwiGLU(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=jnp.float32, name=name)
        gate = nn.silu(dense(cfg.d_ff, "wi_gate")(x))
        up = dense(cfg.d_ff, "wi_up")(x)
        return dense(cfg.d_model, "wo")(gate * up)


class MoE(nn.Module):
    """Top-k mixture of experts with dense one-hot dispatch.

    The dispatch/combine einsums carry an ``experts`` (E) axis that the
    ``ep`` mesh axis shards; under pjit XLA turns the dispatch into the
    token all_to_all the reference's users would hand-build on
    ``hvd.alltoall`` (the reference exposes alltoall exactly for such
    routing, SURVEY §2.7)."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, S, M = x.shape
        E, F, K = cfg.num_experts, cfg.d_ff, cfg.expert_top_k
        router = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                          param_dtype=jnp.float32, name="router")
        logits = router(x.astype(jnp.float32))          # (B, S, E)
        weights, idx = jax.lax.top_k(jax.nn.softmax(logits), K)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        dispatch = jax.nn.one_hot(idx, E, dtype=cfg.dtype)  # (B, S, K, E)
        combine = dispatch * weights[..., None].astype(cfg.dtype)

        wi_gate = self.param("wi_gate", nn.initializers.lecun_normal(),
                             (E, M, F), jnp.float32).astype(cfg.dtype)
        wi_up = self.param("wi_up", nn.initializers.lecun_normal(),
                           (E, M, F), jnp.float32).astype(cfg.dtype)
        wo = self.param("wo", nn.initializers.lecun_normal(),
                        (E, F, M), jnp.float32).astype(cfg.dtype)

        if cfg.moe_capacity_factor > 0:
            # fixed-capacity routing (parallel/moe.py): static
            # (E, C, M) slots, deterministic drop/pad, O(K) expert
            # FLOPs per token — and the slot layout the quantized
            # alltoall exchanges when the ep mesh axis is real.
            # Call-time import: parallel imports models, not the
            # reverse, and moe.py itself is flax-free
            from ..parallel import moe as moe_mod

            T = B * S
            w2, idx2 = moe_mod.top_k_gating(
                logits.reshape(T, E), K)
            cap = moe_mod.expert_capacity(
                T, E, K, cfg.moe_capacity_factor)
            pos, keep, n_dropped = moe_mod.make_dispatch_plan(
                idx2, E, cap)
            slots = moe_mod.moe_dispatch(
                x.reshape(T, M), idx2, pos, keep, E, cap)
            gate = nn.silu(jnp.einsum("ecm,emf->ecf", slots, wi_gate))
            up = jnp.einsum("ecm,emf->ecf", slots, wi_up)
            ye = jnp.einsum("ecf,efm->ecm", gate * up, wo)
            y = moe_mod.moe_combine(ye, idx2, pos, keep, w2)
            self.sow("intermediates", "moe_dropped", n_dropped)
            return y.reshape(B, S, M).astype(cfg.dtype)

        xe = jnp.einsum("bske,bsm->ebsm", dispatch, x)   # route tokens
        gate = nn.silu(jnp.einsum("ebsm,emf->ebsf", xe, wi_gate))
        up = jnp.einsum("ebsm,emf->ebsf", xe, wi_up)
        ye = jnp.einsum("ebsf,efm->ebsm", gate * up, wo)
        return jnp.einsum("bske,ebsm->bsm", combine, ye)


class DecoderBlock(nn.Module):
    cfg: TransformerConfig
    attention_fn: Callable = dense_causal_attention
    decode: bool = False

    @nn.compact
    def __call__(self, x, angles, offset=0):
        cfg = self.cfg
        x = x + Attention(cfg, self.attention_fn, self.decode,
                          name="attn")(
            RMSNorm(cfg.dtype, name="ln_attn")(x), angles, offset)
        mlp = MoE(cfg, name="moe") if cfg.num_experts else \
            SwiGLU(cfg, name="mlp")
        return x + mlp(RMSNorm(cfg.dtype, name="ln_mlp")(x)), None


class TransformerLM(nn.Module):
    """Token ids (B, S) -> logits (B, S, V)."""
    cfg: TransformerConfig

    attention_fn: Callable = dense_causal_attention

    @nn.compact
    def __call__(self, tokens, *, seq_offset=0, decode=False,
                 pre_logits=False):
        cfg = self.cfg
        emb = self.param("embed", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        with jax.named_scope("embed"):
            x = emb[tokens].astype(cfg.dtype)
        angles = jnp.asarray(
            rope_angles(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta))
        angles = jax.lax.dynamic_slice_in_dim(
            angles, seq_offset, tokens.shape[1], axis=0)

        block = DecoderBlock
        if grad_hook.reduces_in_backward() and not self.is_initializing():
            # a data-parallel compiled step is tracing this call: each
            # layer's parameter slice passes the hook inside the scan
            # body (and inside the remat wrapper), so the layer's
            # gradient is all-reduced in the backward loop's body,
            # beside the backward's own work, and not after the loop.
            # Everywhere else the module tree is the plain one
            covered = self.path + ("layers",)
            block = nn.map_variables(
                DecoderBlock, "params",
                trans_in_fn=lambda layer: grad_hook.reduce_in_backward(
                    layer, covered))
        if cfg.remat:
            policy = None
            if cfg.remat_policy == "dots":
                policy = jax.checkpoint_policies.\
                    dots_with_no_batch_dims_saveable
            elif cfg.remat_policy == "dots_flash":
                # "dots" + the flash-attention kernel outputs
                # (checkpoint-named in ops/pallas_kernels.py): a
                # pallas call is not a dot, so without the names the
                # backward replay re-runs every flash forward
                policy = jax.checkpoint_policies.save_from_both_policies(
                    jax.checkpoint_policies.
                    dots_with_no_batch_dims_saveable,
                    jax.checkpoint_policies.save_only_these_names(
                        "flash_out", "flash_lse"))
            elif cfg.remat_policy != "full":
                raise ValueError(
                    f"remat_policy must be 'full', 'dots', or "
                    f"'dots_flash', got {cfg.remat_policy!r}")
            block = nn.remat(block, prevent_cse=False,
                             static_argnums=(), policy=policy)
        stack = nn.scan(
            block,
            variable_axes={"params": 0, "cache": 0},
            split_rngs={"params": True},
            in_axes=nn.broadcast,
            length=cfg.n_layers,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )(cfg, self.attention_fn, decode, name="layers")
        x, _ = stack(x, angles, seq_offset)
        x = RMSNorm(cfg.dtype, name="ln_final")(x)
        if pre_logits:
            # hand the caller the final hidden states + tied embedding
            # so the logits projection can fuse into a chunked loss
            # (chunked_lm_loss) instead of materializing (B, S, V)
            return x, emb
        # logits matmul in the activation dtype with f32 accumulation:
        # a (B*S, M) @ (M, V) f32 matmul would run at a fraction of the
        # MXU's bf16 rate and dominate the step at large vocab
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("bsm,vm->bsv", x,
                                emb.astype(cfg.dtype),
                                preferred_element_type=jnp.float32)
        return logits


def make_generate_fn(model: "TransformerLM", *, max_new_tokens: int,
                     temperature: float = 0.0):
    """Autoregressive decoding with a KV cache (beyond reference —
    the reference is training-only).  Returns
    ``generate(params, prompt_tokens, rng=None) -> (B, max_new_tokens)``.

    Two compiled programs: a prefill over the prompt (populates the
    cache, one chunked attention) and a single-token step reused for
    every position (offset is a traced scalar, so no retracing as the
    sequence grows).  Static shapes throughout: the cache is sized to
    ``cfg.max_seq_len`` up front.
    """
    cfg = model.cfg
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")

    @jax.jit
    def prefill(params, tokens):
        logits, vars_ = model.apply(
            {"params": params}, tokens, decode=True, mutable=["cache"])
        return logits[:, -1], vars_["cache"]

    from functools import partial

    # donate the cache so each step updates it in place instead of
    # copying the full (L, B, max_seq_len, H, D) buffers per token
    @partial(jax.jit, donate_argnums=(1,))
    def step(params, cache, tok, offset):
        logits, vars_ = model.apply(
            {"params": params, "cache": cache}, tok,
            seq_offset=offset, decode=True, mutable=["cache"])
        return logits[:, -1], vars_["cache"]

    def pick(logits, rng):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(rng, logits / temperature, axis=-1)

    def generate(params, prompt_tokens, rng=None):
        if prompt_tokens.shape[1] + max_new_tokens > cfg.max_seq_len:
            raise ValueError(
                f"prompt ({prompt_tokens.shape[1]}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_seq_len "
                f"{cfg.max_seq_len}")
        if temperature != 0.0 and rng is None:
            raise ValueError("sampling (temperature > 0) needs an rng")
        logits, cache = prefill(params, prompt_tokens)
        rngs = jax.random.split(rng, max_new_tokens) \
            if rng is not None else [None] * max_new_tokens
        tok = pick(logits, rngs[0])
        out = [tok]
        offset = jnp.asarray(prompt_tokens.shape[1], jnp.int32)
        for i in range(1, max_new_tokens):
            logits, cache = step(params, cache, tok[:, None], offset)
            tok = pick(logits, rngs[i])
            out.append(tok)
            offset = offset + 1
        return jnp.stack(out, axis=1)

    return generate


def lm_loss(logits, targets):
    """Mean next-token cross-entropy; targets already shifted."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def chunked_lm_loss(x, emb, targets, n_chunks=8, weights=None):
    """Cross-entropy fused with the logits projection, chunked over the
    sequence so the full (B, S, V) logits tensor is never materialized.

    ``lm_loss(model.apply(...), targets)`` stores the f32 logits plus a
    f32 log-softmax — 2 * B*S*V*4 bytes of HBM (2.6 GB at B=5, S=2048,
    V=32k) that caps the trainable batch and adds two full HBM sweeps.
    Here each ``lax.scan`` step projects one sequence chunk, reduces it
    to per-token (logsumexp − target-logit) contributions, and drops
    the chunk logits; ``jax.checkpoint`` re-runs the chunk projection
    in the backward instead of saving it (the logits matmul is ~7% of
    the model's FLOPs, so the recompute costs ~2%).

    Exactly equals ``lm_loss`` in f32 (tests/test_models.py).

    Args:
      x: final hidden states (B, S, M) in the activation dtype
         (``model.apply(..., pre_logits=True)``).
      emb: tied embedding (V, M) f32.
      targets: (B, S) int32 target ids (already shifted).
      n_chunks: sequence chunks; S % n_chunks must be 0.
      weights: optional (B, S) f32 per-token weights — pass 0 for
        padding / the final position when feeding unshifted batches
        (``targets=roll(tokens)``, ``weights[:, -1]=0``); the mean is
        over the weight sum.
    """
    b, s, m = x.shape
    if s % n_chunks:
        raise ValueError(f"seq len {s} not divisible by n_chunks "
                         f"{n_chunks}")
    c = s // n_chunks
    if weights is None:
        weights = jnp.ones((b, s), jnp.float32)

    def chunk_nll(xc, tc, wc):
        # (B, C, M) @ (M, V): f32 accumulation on bf16 operands, same
        # numerics as the unfused logits einsum
        logits = jnp.einsum("bcm,vm->bcv", xc, embd,
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, tc[..., None],
                                  axis=-1)[..., 0]
        return jnp.sum((lse - tgt) * wc)

    def body(total, inp):
        return total + jax.checkpoint(chunk_nll)(*inp), None

    def chunked(a):
        return jnp.moveaxis(a.reshape(b, n_chunks, c, *a.shape[2:]),
                            1, 0)

    with jax.named_scope("lm_head_ce"):
        embd = emb.astype(x.dtype)
        total, _ = jax.lax.scan(
            body, jnp.zeros((), jnp.float32),
            (chunked(x), chunked(targets), chunked(weights)))
        denom = jnp.sum(weights)
        # all-padding batches (weight sum 0) yield loss 0, not 0/0 = NaN
        return total / jnp.where(denom > 0, denom, 1.0)


def make_fused_lm_loss(model: "TransformerLM", n_chunks: int = 16):
    """``loss_fn(params, tokens)`` computing the next-token objective of
    ``lm_loss(model.apply(...)[:, :-1], tokens[:, 1:])`` via
    :func:`chunked_lm_loss` — targets rolled (not sliced, so S stays
    chunkable and sp-shard-aligned) with the final position weighted 0.

    The single definition of the fused objective, shared by
    ``parallel.make_lm_train_step(fused_ce=True)``, the pipelined
    step, and the MFU benchmark so they cannot drift apart.

    ``model`` is a ``TransformerLM`` (flax) or any plain
    ``apply(params, tokens, pre_logits=True) -> (x, emb)`` callable
    (e.g. ``make_pipelined_lm_apply``'s)."""
    if hasattr(model, "apply"):
        def pre(params, tokens):
            return model.apply({"params": params}, tokens,
                               pre_logits=True)
    else:
        def pre(params, tokens):
            return model(params, tokens, pre_logits=True)

    def loss_fn(params, tokens):
        x, emb = pre(params, tokens)
        targets = jnp.roll(tokens, -1, axis=1)
        w = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
        return chunked_lm_loss(x, emb, targets, n_chunks=n_chunks,
                               weights=w)
    return loss_fn
