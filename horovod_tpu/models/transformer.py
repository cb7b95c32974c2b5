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
* Layers that differ (``TransformerConfig.layer_types``, the keys of
  published ``config.json`` files such as Trinity-Mini's ``afmoe``):
  sliding and full attention in one model, leading dense layers, then
  routed experts of which this model may hold a share
  (``parallel/moe.route`` + ``routed_experts_apply``): a sigmoid router
  balanced by a bias, or a softmax router balanced by an auxiliary
  loss, which may read the layer's input before attention
  (SmallThinker's ``config.json``).  Depth still costs no
  compile time: the leading dense layers are one scan, the others one
  scan over the periods of their pattern of kinds.
* A third kind of layer, ``"mamba"`` (Granite-4.0-H's
  ``granitemoehybrid``): a Mamba-2 state-space mixer where the other
  kinds have attention (``models/mamba.py``: in-projection, a causal
  depthwise convolution, the selective scan in its chunked form, a gated
  norm, out-projection), in one period with attention layers; and four
  muP-style multipliers on the embedding, the residual branches, the
  attention scores and the logits.
* Two more kinds (Kimi-Linear's ``kimi_linear``): ``"kda"``, a gated
  delta rule (``models/kda.py``: a state a head that decays by a rate a
  key channel and is corrected by a rank-one delta, in its chunked
  form), and ``"mla"``, latent attention (``MLAAttention``: keys and
  values expanded from one normed low-rank latent, a key part shared by
  the heads, queries and keys wider than the values, no position
  encoding).
* A looped model (``total_ut_steps`` > 1, the key of Ouro's published
  ``config.json``): that stack applied several times over the same
  weights, an exit after every pass, and in ``make_fused_lm_loss`` the
  expected loss under the gate's distribution over the exits.
"""

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..ops import device_sums, grad_hook
from .dense import WRITTEN_BACKWARD_ROWS, dense_product
from .kda import KDA_DEVICE_SUMS, KDAMixer
from .kda import KEPT as KDA_KEPT
from .mamba import SSM_DEVICE_SUMS, Mamba2Mixer
from .mamba import KEPT as SSD_KEPT

#: the names the flash kernels' forward outputs are checkpointed under
#: (``ops/pallas_kernels.py::_flash_vjp_fwd``; the dense reference inner
#: has no such names): every remat policy keeps them (``_with_remat``)
FLASH_KEPT = ("flash_out", "flash_lse")


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
    remat_policy: str = "full"  # every policy keeps every Pallas
    # kernel's outputs by name, so a replay runs no kernel again: the
    # flash kernels' out + lse, a mamba layer's scan output and states.
    # "full" recomputes everything else from the layer's input; with the
    # flash inner it so holds the kernel's output beside that input, as
    # large again (+ lse, 1/64 of it at head_dim 128): still the policy
    # for when memory is shortest, an order of magnitude under "dots".
    # "dots" also saves matmul outputs (jax
    # dots_with_no_batch_dims_saveable) so the backward pass skips
    # re-running the MXU work — ~400MB * n_layers of HBM at (B=8,
    # S=2048, d=1024) for the ~33% remat recompute FLOPs — and a routed
    # layer's named products (``_with_remat``), which are no dots
    # either; "dots_flash" is the same policy under its older name
    head_dim: Optional[int] = None     # None => d_model // n_heads
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True   # False => an output head of its
    # own, the parameter ``lm_head`` (V, M)
    # -- attention, as some published models have it (any layer)
    qk_norm: bool = False         # RMS norm of every query and key head
    # over head_dim, one learned scale each, shared by the heads
    attention_gate: bool = False  # out = (o * sigmoid(x Wg)) Wo
    # -- layers that differ (``layer_types`` set): a stack of
    # ``num_dense_layers`` leading layers with the dense SwiGLU, then
    # the others with routed experts where ``num_experts`` > 0, each
    # layer's attention of its own kind.  The keys mirror the published
    # ``config.json`` of such models; with ``layer_types`` None the
    # model is the one identical block above and these are refused
    layer_types: Optional[tuple] = None   # per layer "sliding_attention"
    # (sees the last ``sliding_window`` positions) | "full_attention" |
    # "mamba" (no attention: the state-space mixer below) | "kda" (none
    # either: the delta rule below) | "mla" (latent attention, below)
    sliding_window: Optional[int] = None
    rope_on_full_attention: bool = True   # False: rotary positions on
    # the sliding layers only
    sandwich_norm: bool = False   # four norms a layer: the attention's
    # and the feed-forward's outputs are normed before the residual add
    mup_enabled: bool = False     # embedding scaled by sqrt(d_model)
    num_dense_layers: int = 0
    moe_intermediate_size: Optional[int] = None   # None => d_ff
    num_shared_experts: int = 0   # a dense SwiGLU of that many expert
    # widths beside the routed ones
    score_func: str = "sigmoid"   # over all num_experts: "sigmoid"
    # scores, or "softmax" (logits; the weights are the softmax over the
    # selected).  Either way the selected are renormalised
    route_norm: bool = True       # (``route_norm``, the only kind built)
    route_scale: float = 1.0      # and then scaled
    router_before_attention: bool = False   # the router reads the
    # layer's INPUT, before its first norm and attention; the experts
    # still take the normed state after attention, with that routing
    expert_activation: str = "silu"   # the routed experts' gate: "silu"
    # (SwiGLU) | "relu" (ReGLU)
    router_aux_loss_coef: float = 0.0   # > 0 (a softmax router): each
    # routed layer's load-balancing loss E * sum_e f_e P_e
    # (``parallel/moe.load_balance_loss``) leaves the stack beside the
    # counts, and ``make_fused_lm_loss`` adds this much times its mean
    # over the routed layers to the cross-entropy
    load_balance_coeff: float = 0.0   # > 0 (a sigmoid router): each
    # routed layer keeps an
    # ``expert_bias`` (collection ``router_state``) that enters its
    # top-k selection only and moves by this much a step toward the
    # experts that got fewer tokens than the mean, where the caller
    # threads the collection (``make_fused_lm_loss(..., with_state=True)``)
    # the share of an expert-parallel deployment this model holds: the
    # router keeps its num_experts outputs and expert_top_k choices a
    # token, the layer computes experts [first_expert_held,
    # first_expert_held + num_experts_held) for the tokens routed to
    # them, and what the absent experts would add is left out
    num_experts_held: Optional[int] = None   # None => all
    first_expert_held: int = 0
    # -- a looped model (a model with ``layer_types``): the whole stack
    # of layers runs ``total_ut_steps`` times over the SAME weights, the
    # final norm after every pass and its output carried into the next;
    # a gate (``early_exit_gate``, d_model -> 1) on every pass's state
    # gives a per-token distribution over the passes to exit at, and
    # ``make_fused_lm_loss`` the expected loss under it less
    # ``exit_entropy_coeff`` times its entropy (arXiv:2510.25741
    # section 3).  The logits are the last pass's
    total_ut_steps: int = 1
    exit_entropy_coeff: float = 0.05
    # -- a state-space layer ("mamba" in ``layer_types``): the Mamba-2
    # mixer of models/mamba.py in attention's place, ``mamba_n_heads``
    # heads of ``mamba_d_head`` with a (d_head, d_state) state each, B
    # and C shared by the heads of a group; the keys are the published
    # ``config.json``'s.  ``mamba_chunk_size`` is the chunk of the
    # scan's chunked form: the program's to choose, it changes no
    # result beyond rounding.  ``mamba_n_groups`` is kept as a published
    # key: the benchmark's one such model has ONE group, more are held
    # to the recurrence by the CPU tests alone
    mamba_n_heads: int = 0
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    # -- a delta-rule layer ("kda" in ``layer_types``): the mixer of
    # models/kda.py in attention's place, ``kda_n_heads`` heads with a
    # (kda_d_head, kda_d_head) state each and causal convolutions of
    # ``kda_d_conv`` taps (None: 4); the keys mirror the published
    # ``linear_attn_config``.  ``kda_chunk_size`` (None: 64; a power of
    # two) is the chunk of the rule's chunked form: the program's to
    # choose, it changes no result beyond rounding.  Each key is refused
    # in a model with no such layer
    kda_n_heads: Optional[int] = None
    kda_d_head: Optional[int] = None
    kda_d_conv: Optional[int] = None
    kda_chunk_size: Optional[int] = None
    # -- a latent-attention layer ("mla" in ``layer_types``;
    # ``MLAAttention``): ``n_heads`` heads whose keys and values are
    # expanded from ONE normed latent of ``kv_lora_rank`` a token: a key
    # part of ``qk_nope_head_dim`` and a value of ``v_head_dim`` a head,
    # beside a key part of ``qk_rope_head_dim`` that all the heads share
    # (the published key's name; this model applies NO rotary position
    # to it, Kimi-Linear's ``mla_use_nope``).  Queries are projected
    # from the layer's input at the keys' width (``q_lora_rank`` null).
    # Each key is refused in a model with no such layer
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    # -- muP-style multipliers (Granite's ``config.json``), each a no-op
    # at its default: the embedding times ``embedding_multiplier``, every
    # layer's two branch outputs times ``residual_multiplier`` before
    # the residual add, attention scores scaled by
    # ``attention_multiplier`` in place of 1 / sqrt(head_dim), the
    # logits divided by ``logits_scaling``
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.n_heads)
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types",
                               tuple(self.layer_types))

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
    """x: (B, S, H, D); angles: (S, D//2), or (B, S, D//2) where each
    row of the batch sits at positions of its own (a serving tick's
    slots) — rotate pairs of channels."""
    heads = (None if angles.ndim == 2 else slice(None), slice(None), None)
    sin = jnp.sin(angles)[heads]
    cos = jnp.cos(angles)[heads]
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
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1,
                                         keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype)


class Attention(nn.Module):
    cfg: TransformerConfig
    attention_fn: Callable = dense_causal_attention
    decode: bool = False      # KV-cache autoregressive path
    layer_type: Optional[str] = None   # None: the model-wide
    # ``attention_window`` and rotary positions; else this layer's kind
    # of ``cfg.layer_types``
    dot_general: Optional[Callable] = None    # the projections' product
    # (None: flax's own): ``LayerPeriod`` chooses

    # ``__call__`` is ``_qkv``, an attention inner, ``_out``; a caller
    # with a cache of its own (serving/kvcache.py) puts ITS inner
    # between ``qkv`` and ``out``

    @nn.nowrap
    def _heads(self, feats, name):
        return nn.DenseGeneral(
            feats, axis=-1, use_bias=False, dtype=self.cfg.dtype,
            param_dtype=jnp.float32, dot_general=self.dot_general, name=name)

    @nn.nowrap
    def _qkv(self, x, angles):
        cfg = self.cfg
        H, D = cfg.n_heads, cfg.head_dim
        KV = cfg.kv_heads          # == H unless GQA/MQA configured
        q = self._heads((H, D), "wq")(x)
        k = self._heads((KV, D), "wk")(x)
        v = self._heads((KV, D), "wv")(x)
        if cfg.qk_norm:
            q = RMSNorm(cfg.dtype, cfg.rms_norm_eps, name="q_norm")(q)
            k = RMSNorm(cfg.dtype, cfg.rms_norm_eps, name="k_norm")(k)
        if self.layer_type in (None, "sliding_attention") \
                or cfg.rope_on_full_attention:
            q = apply_rope(q, angles)
            k = apply_rope(k, angles)
        if cfg.attention_multiplier is not None:
            # every attention inner (dense, flash, ring, the decode
            # path) scales its scores by 1 / sqrt(D): q carries the
            # rest, so none of them changes.  Granite's 1/64 at D = 64
            # makes the factor 0.125, a power of two and exact in
            # bfloat16; another value rounds q once more
            q = q * (cfg.attention_multiplier * np.sqrt(D))
        return q, k, v

    @nn.nowrap
    def _out(self, x, o):
        cfg = self.cfg
        if cfg.attention_gate:
            o = o * nn.sigmoid(
                self._heads((cfg.n_heads, cfg.head_dim), "wg")(x))
        return nn.DenseGeneral(cfg.d_model, axis=(-2, -1), use_bias=False,
                               dtype=cfg.dtype, param_dtype=jnp.float32,
                               dot_general=self.dot_general, name="wo")(o)

    @nn.compact
    def qkv(self, x, angles):
        """Before the inner: q ``(B, S, H, D)``, k and v at the KV
        heads' own width ``(B, S, KV, D)``, never expanded."""
        return self._qkv(x, angles)

    @nn.compact
    def out(self, x, o):
        """After it: the gate on ``x``, the projection of ``o``."""
        return self._out(x, o)

    @nn.compact
    def __call__(self, x, angles, offset=0):
        cfg = self.cfg
        H, D, KV = cfg.n_heads, cfg.head_dim, cfg.kv_heads
        window = cfg.attention_window
        if self.layer_type is not None:
            window = cfg.sliding_window \
                if self.layer_type == "sliding_attention" else None
        q, k, v = self._qkv(x, angles)

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
                    q, ck.value, cv.value, offset=offset, window=window)
            else:
                o = grouped_causal_attention(
                    q, ck.value, cv.value, offset=offset, window=window)
        else:
            if window is not None:
                # config-driven sliding window: forwarded to inners
                # that accept it (dense reference, pallas flash); the
                # sequence-parallel inners (ring/ulysses) don't — a
                # silent full-causal fallback would train a different
                # model than the config says, so fail loudly
                try:
                    o = self.attention_fn(
                        q, expand_kv(k), expand_kv(v), window=window)
                except TypeError as exc:
                    raise ValueError(
                        f"attention_window={window} "
                        f"set but attention_fn "
                        f"{getattr(self.attention_fn, '__name__', self.attention_fn)!r} "
                        f"does not accept a window= kwarg (ring/"
                        f"ulysses sequence parallelism does not "
                        f"support sliding windows)") from exc
            else:
                o = self.attention_fn(q, expand_kv(k), expand_kv(v))
        return self._out(x, o)


class MLAAttention(nn.Module):
    """Latent attention without position encoding (the ``"mla"`` kind;
    DeepSeek-V2's multi-head latent attention as Kimi-Linear runs it)::

        q = x Wq -> H heads of (nope + rope)
        [c | k_s] = x Wkv_a   (kv_lora_rank | rope);  c = rmsnorm(c)
        [k_n | v] = c Wkv_b -> H heads of (nope | v_head_dim)
        k = [k_n | k_s for every head]
        out = concat(causal_softmax(q k^T / sqrt(nope + rope)) v) Wo

    The attention inners take ONE head size: ``v`` goes in filled up
    with zeros to the keys' width and the output's filled columns are
    dropped, which is exact (a zero column of ``v`` is a zero column of
    ``P v``) and costs the ``P v`` products (nope + rope) / v_head_dim
    times their FLOPs; the inner's own 1 / sqrt(head size) is then the
    published scale."""
    cfg: TransformerConfig
    attention_fn: Callable = dense_causal_attention
    dot_general: Optional[Callable] = None    # as ``Attention``'s

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        heads, rank = cfg.n_heads, cfg.kv_lora_rank
        nope, shared = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        value = cfg.v_head_dim
        if not rank or not nope or not value or shared is None \
                or value > nope + shared:
            raise ValueError(
                "an mla layer needs kv_lora_rank, qk_nope_head_dim, "
                "qk_rope_head_dim and a v_head_dim no wider than its "
                f"keys, got {rank}, {nope}, {shared}, {value}")

        def dense(feats, name, axis=-1):
            return nn.DenseGeneral(
                feats, axis=axis, use_bias=False, dtype=cfg.dtype,
                param_dtype=jnp.float32, dot_general=self.dot_general,
                name=name)

        q = dense((heads, nope + shared), "wq")(x)
        latent, k_shared = jnp.split(dense(rank + shared, "kv_a")(x),
                                     (rank,), axis=-1)
        latent = RMSNorm(cfg.dtype, cfg.rms_norm_eps, name="kv_norm")(latent)
        k_own, v = jnp.split(dense((heads, nope + value), "kv_b")(latent),
                             (nope,), axis=-1)
        k = jnp.concatenate([k_own, jnp.broadcast_to(
            k_shared[:, :, None, :], k_own.shape[:-1] + (shared,))], axis=-1)
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, nope + shared - value),))
        o = self.attention_fn(q, k, v)[..., :value]
        return dense(cfg.d_model, "wo", axis=(-2, -1))(o)


class SwiGLU(nn.Module):
    cfg: TransformerConfig
    d_ff: Optional[int] = None    # None => cfg.d_ff
    dot_general: Optional[Callable] = None    # as ``Attention``'s

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d_ff = self.d_ff or cfg.d_ff
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=jnp.float32, dot_general=self.dot_general, name=name)
        gate = nn.silu(dense(d_ff, "wi_gate")(x))
        up = dense(d_ff, "wi_up")(x)
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
    """One layer: ``__call__`` around the model's own attention;
    ``qkv`` and ``finish`` are its two halves for a caller whose inner
    goes around a cache of its own (serving/kvcache.py)."""
    cfg: TransformerConfig
    attention_fn: Callable = dense_causal_attention
    decode: bool = False

    @nn.nowrap
    def _attn(self):
        return Attention(self.cfg, self.attention_fn, self.decode,
                         name="attn")

    @nn.nowrap
    def _ln_attn(self, x):
        return RMSNorm(self.cfg.dtype, self.cfg.rms_norm_eps,
                       name="ln_attn")(x)

    @nn.nowrap
    def _feed_forward(self, x, attended):
        cfg = self.cfg
        x = _residual(cfg, x, attended)
        mlp = MoE(cfg, name="moe") if cfg.num_experts else \
            SwiGLU(cfg, name="mlp")
        return _residual(cfg, x, mlp(RMSNorm(
            cfg.dtype, cfg.rms_norm_eps, name="ln_mlp")(x)))

    @nn.compact
    def __call__(self, x, angles, offset=0):
        return self._feed_forward(x, self._attn()(
            self._ln_attn(x), angles, offset)), None

    @nn.compact
    def qkv(self, x, angles):
        """``(h, q, k, v)``: the normed input, ``Attention.qkv`` of it."""
        h = self._ln_attn(x)
        return (h,) + self._attn().qkv(h, angles)

    @nn.compact
    def finish(self, x, h, o):
        """The layer's output from the inner's ``o``."""
        return self._feed_forward(x, self._attn().out(h, o))


def _residual(cfg, x, branch):
    """``x + residual_multiplier * branch``."""
    if cfg.residual_multiplier != 1.0:
        branch = branch * cfg.residual_multiplier
    return x + branch


LAYER_TYPES = ("sliding_attention", "full_attention", "mamba", "kda", "mla")

#: the configuration keys that only one kind of layer reads: set in a
#: model with no layer of that kind, each is refused by name
_KEYS_OF_KIND = {
    "kda": ("kda_n_heads", "kda_d_head", "kda_d_conv", "kda_chunk_size"),
    "mla": ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim"),
}


def _check_keys_of_kinds(cfg):
    kinds = cfg.layer_types or ()
    for kind, keys in _KEYS_OF_KIND.items():
        unread = [key for key in keys if getattr(cfg, key) is not None]
        if kind not in kinds and unread:
            raise ValueError(
                f"{', '.join(unread)}: no layer of this model reads "
                f"{'them' if len(unread) > 1 else 'it'} (set layer_types "
                f"with a {kind!r} layer)")

#: the collection of what a routed layer's training loop keeps beside
#: its parameters: ``expert_bias`` (num_experts,) a layer
ROUTER_STATE = "router_state"

#: the sums the routed layers make on the device, a step call
#: (``ops/device_sums.py``; docs/observability.md "The compiled step")
MOE_DEVICE_SUMS = ("horovod_moe_assignments_total",
                   "horovod_moe_held_assignments_total",
                   "horovod_moe_dropped_assignments_total",
                   # the passes the held assignments took through their
                   # buffer, and those beyond a layer's first, whose
                   # forward the backward pass runs again
                   "horovod_moe_passes_total",
                   "horovod_moe_recomputed_passes_total")
#: and where the router is balanced by an auxiliary loss
#: (``router_aux_loss_coef`` > 0): the sum over the routed layers of
#: that loss (a fraction: 1.0 a layer under a balanced router) and of
#: the tokens the busiest of ALL the router's experts got
MOE_AUX_LOSS_SUM = "horovod_moe_aux_loss_total"
MOE_MAX_EXPERT_TOKENS_SUM = "horovod_moe_max_expert_tokens_total"


def _layer_sums(cfg, counts=0, aux_loss=0, max_expert_tokens=0, ssm=0,
                kda=0):
    """What a layer hands up the stack beside its output, summed over
    the layers on the way: the routed layer's counts
    (``MOE_DEVICE_SUMS``), where the router has an auxiliary loss, that
    loss and the busiest expert's tokens, and in a model with mamba or
    kda layers what their scans processed (``SSM_DEVICE_SUMS``,
    ``KDA_DEVICE_SUMS``).  The defaults are the sums' zero."""
    sums = {"counts": counts}
    if cfg.router_aux_loss_coef:
        sums.update(aux_loss=aux_loss, max_expert_tokens=max_expert_tokens)
    if "mamba" in cfg.layer_types:
        sums["ssm"] = ssm
    if "kda" in cfg.layer_types:
        sums["kda"] = kda
    return sums


def _expert_bias(module):
    """A routed layer's ``expert_bias`` variable, or None where the
    model keeps none or the caller applied it without the collection."""
    if not module.cfg.load_balance_coeff or not (
            module.is_initializing()
            or module.has_variable(ROUTER_STATE, "expert_bias")):
        return None
    return module.variable(ROUTER_STATE, "expert_bias", jnp.zeros,
                           (module.cfg.num_experts,), jnp.float32)


class RoutedExperts(nn.Module):
    """The feed-forward of an expert layer: a router over all
    ``num_experts`` (``parallel/moe.route``: ``cfg.score_func``), the
    routed experts this model holds (the dropless grouped product of
    ``parallel/moe.routed_experts_apply``) and the shared experts
    beside them.  ``route_only`` stops after the routing of ``x`` and
    returns it; handed back as ``routing`` with the tensor the experts
    take, the layer goes on from there (a router that reads the layer's
    input before attention).  Returns ``(y, sums)``, ``sums`` as
    ``_layer_sums``: ``counts`` int32 (5,) are the assignments, those
    on held experts, those of them not computed, the passes through the
    buffer and those of them beyond the first (``MOE_DEVICE_SUMS``)."""
    cfg: TransformerConfig
    dot_general: Optional[Callable] = None    # the shared experts'

    @nn.compact
    def __call__(self, x, routing=None, route_only=False):
        # call-time import: parallel imports models, not the reverse
        from ..parallel import moe as moe_mod

        cfg = self.cfg
        B, S, M = x.shape
        E = cfg.num_experts
        held = cfg.num_experts_held or E
        F = cfg.moe_intermediate_size or cfg.d_ff
        init = nn.initializers.lecun_normal()
        flat = x.reshape(B * S, M)
        if routing is None:
            router = self.param("router", init, (M, E), jnp.float32)
        if not route_only:
            experts = [
                self.param(name, init, shape, jnp.float32).astype(cfg.dtype)
                for name, shape in (("wi_gate", (held, M, F)),
                                    ("wi_up", (held, M, F)),
                                    ("wo", (held, F, M)))]
        # expert_bias enters the selection only.  Its update is a rule
        # of the training loop: the layer reads it from ``router_state``
        # and, where the caller made that collection mutable, leaves the
        # updated one there; without the collection it is zero
        state = _expert_bias(self)
        if routing is None:
            bias = jnp.zeros((E,), jnp.float32) \
                if cfg.score_func == "sigmoid" else None
            if state is not None:
                bias = state.value
            weights, idx, tokens_per_expert, mean_probs = moe_mod.route(
                flat, router, cfg.expert_top_k,
                score_func=cfg.score_func, expert_bias=bias,
                route_scale=cfg.route_scale)
            balance = {}
            if cfg.router_aux_loss_coef:
                balance = dict(
                    aux_loss=moe_mod.load_balance_loss(tokens_per_expert,
                                                       mean_probs),
                    max_expert_tokens=jnp.max(tokens_per_expert))
            routing = (weights, idx, tokens_per_expert, balance)
        if route_only:
            return routing
        weights, idx, tokens_per_expert, balance = routing
        y, counts = moe_mod.routed_experts_apply(
            flat, weights, idx, *experts, num_experts=E,
            first_expert=cfg.first_expert_held,
            activation=cfg.expert_activation)
        if state is not None and not self.is_initializing() \
                and self.is_mutable_collection(ROUTER_STATE):
            state.value = moe_mod.updated_expert_bias(
                state.value, tokens_per_expert, cfg.load_balance_coeff)
        y = checkpoint_name(y.reshape(B, S, M).astype(cfg.dtype),
                            moe_mod.KEPT_OUTPUT)
        if cfg.num_shared_experts:
            y = y + SwiGLU(cfg, F * cfg.num_shared_experts,
                           self.dot_general, name="shared")(x)
        return y, _layer_sums(cfg, counts, **balance)


class LayeredBlock(nn.Module):
    """One layer of a model whose layers differ: attention of this
    layer's kind, the state-space mixer or the delta rule, then the
    dense SwiGLU or the
    routed experts (routed, under ``router_before_attention``, from the
    layer's input)."""
    cfg: TransformerConfig
    attention_fn: Callable
    layer_type: str
    routed: bool
    dot_general: Optional[Callable] = None    # every dense projection's

    @nn.compact
    def __call__(self, x, angles):
        cfg = self.cfg

        def norm(name):
            return RMSNorm(cfg.dtype, cfg.rms_norm_eps, name=name)

        routing = None
        if self.routed:
            moe = RoutedExperts(cfg, self.dot_general, name="moe")
            if cfg.router_before_attention:
                # issued ahead of attention, under the routed layer's
                # own scope ``moe/route`` all the same
                routing = moe(x, route_only=True)
        scanned = {}
        if self.layer_type == "mamba":
            # the module's name is the scope: ``attn`` would book the
            # mixer to the attention's device time
            h, scanned["ssm"] = Mamba2Mixer(
                cfg, self.dot_general, name="mamba")(norm("ln_mamba")(x))
        elif self.layer_type == "kda":
            h, scanned["kda"] = KDAMixer(
                cfg, self.dot_general, name="kda")(norm("ln_kda")(x))
        elif self.layer_type == "mla":
            with jax.named_scope("mla"):
                h = MLAAttention(cfg, self.attention_fn, self.dot_general,
                                 name="attn")(norm("ln_attn")(x))
        else:
            # the device trace's path carries the layer's published kind
            with jax.named_scope(self.layer_type):
                h = Attention(cfg, self.attention_fn,
                              layer_type=self.layer_type,
                              dot_general=self.dot_general,
                              name="attn")(norm("ln_attn")(x), angles)
        if cfg.sandwich_norm:
            h = norm("ln_post_attn")(h)
        x = _residual(cfg, x, h)
        m = norm("ln_mlp")(x)
        if self.routed:
            f, sums = moe(m, routing)
        else:
            f, sums = SwiGLU(cfg, dot_general=self.dot_general,
                             name="mlp")(m), _layer_sums(
                cfg, jnp.zeros((len(MOE_DEVICE_SUMS),), jnp.int32),
                jnp.float32(0), jnp.int32(0))
        for key, names in (("ssm", SSM_DEVICE_SUMS),
                           ("kda", KDA_DEVICE_SUMS)):
            if key in sums:
                sums[key] = scanned[key] if key in scanned \
                    else jnp.zeros((len(names),), jnp.int32)
        if cfg.sandwich_norm:
            f = norm("ln_post_mlp")(f)
        return _residual(cfg, x, f), sums


class LayerPeriod(nn.Module):
    """The layers ``layer_0`` .. of one repetition of a pattern of
    kinds: the body a scan over the depth repeats."""
    cfg: TransformerConfig
    attention_fn: Callable
    layer_types: tuple
    routed: bool
    repeats: int = 1

    @nn.compact
    def __call__(self, x, angles):
        # a scan of one iteration is no loop to the compiler, which
        # would then merge each layer's recomputation with its forward
        # pass and keep every activation after all
        block = _with_remat(LayeredBlock, self.cfg,
                            prevent_cse=self.repeats == 1)
        # the projections' product by the rows a step's product
        # contracts over (models/dense.py has the measurements)
        product = dense_product if x.shape[0] * x.shape[1] \
            <= WRITTEN_BACKWARD_ROWS else None
        sums = _layer_sums(self.cfg)
        for i, kind in enumerate(self.layer_types):
            x, layer = block(self.cfg, self.attention_fn, kind, self.routed,
                             product, name=f"layer_{i}")(x, angles)
            sums = jax.tree.map(jnp.add, sums, layer)
        return x, sums


def _with_remat(block, cfg, prevent_cse=False):
    """``block`` under ``cfg``'s remat policy (inside a scan of several
    iterations the loop itself keeps the recomputation apart from the
    forward pass, and ``prevent_cse`` can stay off)."""
    if not cfg.remat:
        return block
    # call-time import: parallel imports models, not the reverse
    from ..parallel.moe import KEPT_OUTPUT, KEPT_PRODUCTS

    # what a policy keeps beside the dense products, by the names the
    # values are checkpointed under: neither a pallas call
    # (ops/pallas_kernels.py) nor a grouped product (parallel/moe.py)
    # is a dot, so without its name the backward replay runs it again
    routed = KEPT_PRODUCTS + (KEPT_OUTPUT,)
    # one rule for the Pallas kernels: EVERY policy keeps their outputs,
    # "full" too, and a replay runs no kernel's forward again.  The
    # flash kernels' out + lse are a layer's input over again (16.8 +
    # 0.26 MB a layer application at 4,096 tokens x 2,048) for half the
    # forward kernel's time; the names exist only where the attention
    # inner is the flash kernel.  A model with mamba layers keeps their
    # scans' outputs and chunk states (2 x 67 MB a layer at 8,192
    # tokens), one with kda layers theirs (134 + 268 MB a layer at
    # 16,384 tokens); no other program sees those names
    kinds = cfg.layer_types or ()
    scans = (SSD_KEPT if "mamba" in kinds else ()) \
        + (KDA_KEPT if "kda" in kinds else ())
    kernels = FLASH_KEPT + scans
    dots = kernels + routed     # one policy under two names
    kept = {"full": kernels, "dots": dots, "dots_flash": dots}
    if cfg.remat_policy not in kept:
        raise ValueError(
            f"remat_policy must be 'full', 'dots', or 'dots_flash', "
            f"got {cfg.remat_policy!r}")
    policy = jax.checkpoint_policies.save_only_these_names(
        *kept[cfg.remat_policy])
    if cfg.remat_policy != "full":
        policy = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            policy)
    return nn.remat(block, prevent_cse=prevent_cse,
                    static_argnums=(), policy=policy)


def _shortest_period(kinds):
    """The shortest prefix of ``kinds`` whose repetition is ``kinds``,
    and how often it repeats."""
    n = len(kinds)
    for p in range(1, n + 1):
        if n % p == 0 and kinds == kinds[:p] * (n // p):
            return kinds[:p], n // p


def _stack(module, block, name, length, remat=False, hook=True,
           **scan_axes):
    """``block`` (under the remat policy with ``remat``) scanned
    ``length`` times over a leading axis of its parameters inside
    ``module``, each iteration's slice passing the step's gradient hook
    where one is tracing and ``hook`` says this is the slice's only
    use."""
    if hook and grad_hook.reduces_in_backward() \
            and not module.is_initializing():
        # a data-parallel compiled step is tracing this call: each
        # layer's parameter slice passes the hook inside the scan
        # body (and inside the remat wrapper), so the layer's
        # gradient is all-reduced in the backward loop's body,
        # beside the backward's own work, and not after the loop.
        # Everywhere else the module tree is the plain one
        covered = module.path + (name,)
        block = nn.map_variables(
            block, "params",
            trans_in_fn=lambda layer: grad_hook.reduce_in_backward(
                layer, covered))
    if remat:
        block = _with_remat(block, module.cfg)
    return nn.scan(
        block,
        variable_axes={"params": 0, **scan_axes},
        split_rngs={"params": True},
        in_axes=nn.broadcast,
        length=length,
        metadata_params={nn.PARTITION_NAME: "layers"})


def _check_router(cfg):
    """Refuse a routed layer this model does not build."""
    from ..parallel import moe as moe_mod

    if cfg.score_func not in moe_mod.SCORE_FUNCS or not cfg.route_norm:
        raise ValueError(
            f"the routed layer scores with one of {moe_mod.SCORE_FUNCS} "
            "and renormalises the selected: "
            f"score_func={cfg.score_func!r}, route_norm={cfg.route_norm}")
    if cfg.expert_activation not in moe_mod.ACTIVATIONS:
        raise ValueError(
            f"expert_activation must be one of "
            f"{tuple(moe_mod.ACTIVATIONS)}, got {cfg.expert_activation!r}")
    sigmoid = cfg.score_func == "sigmoid"
    if (cfg.load_balance_coeff and not sigmoid) or (
            cfg.router_aux_loss_coef and sigmoid):
        raise ValueError(
            "a sigmoid router is balanced by its expert_bias "
            "(load_balance_coeff), a softmax router by its auxiliary "
            f"loss (router_aux_loss_coef): score_func={cfg.score_func!r}, "
            f"load_balance_coeff={cfg.load_balance_coeff}, "
            f"router_aux_loss_coef={cfg.router_aux_loss_coef}")


def _layered(module, x, angles):
    """The stack of a model whose layers differ, built inside
    ``module`` (which has ``cfg`` and ``attention_fn``): the leading
    dense layers one scan, the others one scan over the periods of
    their pattern of kinds, so depth costs no compile time.  Returns
    ``(x, the layers' sums)`` (``_layer_sums``: what the routed layers
    counted, and their auxiliary losses where the router has one)."""
    cfg = module.cfg
    kinds, lead = cfg.layer_types, cfg.num_dense_layers
    if len(kinds) != cfg.n_layers or set(kinds) - set(LAYER_TYPES):
        raise ValueError(
            f"layer_types must name n_layers={cfg.n_layers} kinds "
            f"of {LAYER_TYPES}, got {kinds}")
    if "sliding_attention" in kinds and not cfg.sliding_window:
        raise ValueError("sliding_attention layers need "
                         "sliding_window")
    if {"mamba", "kda"} & set(kinds) and cfg.total_ut_steps > 1:
        raise ValueError(
            "a looped model (total_ut_steps > 1) has no mamba or kda "
            "layers: the device's sums count one pass")
    _check_keys_of_kinds(cfg)
    if cfg.num_experts:
        _check_router(cfg)
    groups = [("dense_layers", kinds[:lead], False),
              ("periods", kinds[lead:],
               bool(cfg.num_experts))]
    sums = _layer_sums(cfg)
    for name, group, routed in groups:
        if not group:
            continue
        period, repeats = _shortest_period(group)
        # a layer that runs total_ut_steps times has its gradient
        # whole only when the backward of the FIRST pass leaves it:
        # the step reduces it after the backward, once
        # (docs/parallelism.md)
        stack = _stack(module, LayerPeriod, name, repeats,
                       hook=cfg.total_ut_steps == 1,
                       **{ROUTER_STATE: 0})(
            cfg, module.attention_fn, period, routed, repeats, name=name)
        x, periods = stack(x, angles)
        sums = jax.tree.map(lambda total, each: total + jnp.sum(each, axis=0),
                            sums, periods)
    return x, sums


class LoopPass(nn.Module):
    """One pass of a looped model: the whole stack of layers, then the
    final norm, whose output is both the next pass's input and this
    pass's exit."""
    cfg: TransformerConfig
    attention_fn: Callable

    @nn.compact
    def __call__(self, x, angles):
        x, _ = _layered(self, x, angles)
        return RMSNorm(self.cfg.dtype, self.cfg.rms_norm_eps,
                       name="ln_final")(x)


#: what a looped model sums on the device, a step call
#: (``ops/device_sums.py``): over the tokens the loss weighs, the
#: weights and the mass of the exit distribution on each pass
LOOP_TOKENS_SUM = "horovod_loop_tokens_total"
#: what a step call of a looped model counts on the host: its layers
#: times its passes
LOOP_LAYER_APPLICATIONS = "horovod_loop_layer_applications_total"


def loop_exit_mass_sum(step):
    """The name of the sum of pass ``step``'s exit mass (from 1)."""
    return f"horovod_loop_exit_mass_pass_{step}_total"


def loop_device_sums(total_ut_steps):
    return (LOOP_TOKENS_SUM,) + tuple(
        loop_exit_mass_sum(t + 1) for t in range(total_ut_steps))


def embed_tokens(cfg, emb, tokens):
    """``tokens``' rows of ``emb`` (V, M) under the config's
    multipliers, in ``cfg.dtype``: the model's first step, also for a
    caller that runs the layers itself (serving/kvcache.py)."""
    with jax.named_scope("embed"):
        x = emb[tokens]
        if cfg.mup_enabled:
            x = x * np.sqrt(cfg.d_model).astype(np.float32)
        if cfg.embedding_multiplier != 1.0:
            x = x * np.float32(cfg.embedding_multiplier)
        return x.astype(cfg.dtype)


def lm_logits(cfg, x, head):
    """Float32 logits of the normed states ``x`` against ``head``
    (V, M), the tied embedding or ``lm_head``: the model's last step."""
    # logits matmul in the activation dtype with f32 accumulation:
    # a (B*S, M) @ (M, V) f32 matmul would run at a fraction of the
    # MXU's bf16 rate and dominate the step at large vocab
    with jax.named_scope("lm_head"):
        logits = jnp.einsum("bsm,vm->bsv", x, head.astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return logits


class TransformerLM(nn.Module):
    """Token ids (B, S) -> logits (B, S, V)."""
    cfg: TransformerConfig

    attention_fn: Callable = dense_causal_attention

    @property
    def device_sums(self):
        """The names of the sums this model makes on the device inside
        a compiled step (``ops/device_sums.py``)."""
        cfg = self.cfg
        if cfg.total_ut_steps > 1:
            return loop_device_sums(cfg.total_ut_steps)
        if cfg.layer_types is None:
            return ()
        names = ()
        if self.routed_layers:
            names += MOE_DEVICE_SUMS + (
                (MOE_AUX_LOSS_SUM, MOE_MAX_EXPERT_TOKENS_SUM)
                if cfg.router_aux_loss_coef else ())
        if "mamba" in cfg.layer_types:
            names += SSM_DEVICE_SUMS
        if "kda" in cfg.layer_types:
            names += KDA_DEVICE_SUMS
        return names

    @property
    def routed_layers(self):
        """How many of the layers have routed experts."""
        cfg = self.cfg
        return max(cfg.n_layers - cfg.num_dense_layers, 0) \
            if cfg.num_experts else 0

    def _loop(self, x, angles):
        """The stack of a looped model: ONE ``LoopPass`` applied
        ``total_ut_steps`` times, so every pass reads the same
        parameters and their gradient is the sum over the passes.
        Returns the normed state after every pass, (R, B, S, M).  (A
        scan over the passes with the parameters broadcast computes the
        same in 4% more time on the chip, PERF.md section 6.)"""
        cfg = self.cfg
        if cfg.num_experts:
            raise ValueError(
                "a looped model (total_ut_steps > 1) has no routed "
                "experts: expert_bias and the device's sums count one "
                "pass")
        one_pass = LoopPass(cfg, self.attention_fn, name="loop")
        states = []
        for _ in range(cfg.total_ut_steps):
            x = one_pass(x, angles)
            states.append(x)
        return jnp.stack(states)

    @nn.compact
    def __call__(self, tokens, *, seq_offset=0, decode=False,
                 pre_logits=False):
        cfg = self.cfg
        emb = self.param("embed", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        head = emb if cfg.tie_word_embeddings else self.param(
            "lm_head", nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.d_model), jnp.float32)
        x = embed_tokens(cfg, emb, tokens)
        angles = jnp.asarray(
            rope_angles(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta))
        angles = jax.lax.dynamic_slice_in_dim(
            angles, seq_offset, tokens.shape[1], axis=0)

        looped, aux_loss = cfg.total_ut_steps > 1, None
        if looped and cfg.layer_types is None:
            raise ValueError(
                "total_ut_steps belongs to a model with layer_types")
        if cfg.layer_types is not None and decode:
            raise ValueError(
                "a model with layer_types has no KV-cache path: two "
                "kinds of layer in one cache, and a looped model one "
                "set of keys and values a pass (serving/kvcache.py)")
        if looped:
            states = self._loop(x, angles)
            with jax.named_scope("exit_gate"):
                # float32, as a router's product: a pass's gate is one
                # number a token
                gates = nn.Dense(1, dtype=jnp.float32,
                                 param_dtype=jnp.float32,
                                 name="early_exit_gate")(states)[..., 0]
            if pre_logits:
                # the normed state (R, B, S, M) and the gate's logit
                # (R, B, S) of EVERY pass
                return (states, gates), head
            x = states[-1]
        elif cfg.layer_types is not None:
            x, sums = _layered(self, x, angles)
            if self.routed_layers:
                for name, value in zip(MOE_DEVICE_SUMS, sums["counts"]):
                    device_sums.add(name, value)
            for key, names in (("ssm", SSM_DEVICE_SUMS),
                               ("kda", KDA_DEVICE_SUMS)):
                if key in sums:
                    for name, value in zip(names, sums[key]):
                        device_sums.add(name, value)
            if cfg.router_aux_loss_coef and self.routed_layers:
                device_sums.add_fraction(MOE_AUX_LOSS_SUM, sums["aux_loss"])
                device_sums.add(MOE_MAX_EXPERT_TOKENS_SUM,
                                sums["max_expert_tokens"])
                aux_loss = sums["aux_loss"] / self.routed_layers
        else:
            if cfg.sandwich_norm or cfg.num_dense_layers \
                    or cfg.num_shared_experts or cfg.mup_enabled \
                    or cfg.num_experts_held is not None:
                raise ValueError(
                    "sandwich_norm, mup_enabled, num_dense_layers, "
                    "num_shared_experts and num_experts_held belong "
                    "to a model with layer_types")
            _check_keys_of_kinds(cfg)
            stack = _stack(self, DecoderBlock, "layers", cfg.n_layers,
                           remat=True, cache=0)(
                cfg, self.attention_fn, decode, name="layers")
            x, _ = stack(x, angles, seq_offset)
        if not looped:
            x = RMSNorm(cfg.dtype, cfg.rms_norm_eps, name="ln_final")(x)
        if pre_logits:
            # hand the caller the final hidden states + the output
            # head (the tied embedding, or ``lm_head``) so the logits
            # projection can fuse into a chunked loss
            # (chunked_lm_loss) instead of materializing (B, S, V);
            # beside the states, where the router has one, the mean over
            # the routed layers of its auxiliary loss
            return (x if aux_loss is None else (x, aux_loss)), head
        return lm_logits(cfg, x, head)


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


def exit_distribution(gates):
    """The distribution over the passes a looped model's token exits
    at, from the gate's logits ``gates`` (R, ...) with the passes
    leading: ``p_t = sigmoid(g_t) prod_{j<t} (1 - sigmoid(g_j))`` and
    the last pass takes what is left, so every token's sum to 1.
    Returns ``(p, entropy)``, float32, (R, ...) and (...)."""
    gates = gates.astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gates), axis=0)   # log prod(1 - l)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]])
    log_p = jnp.concatenate(
        [jax.nn.log_sigmoid(gates[:-1]) + before[:-1], before[-1:]])
    p = jnp.exp(log_p)
    return p, -jnp.sum(p * log_p, axis=0)


def _seq_chunks(n_chunks, *arrays):
    """Each (B, S, ...) -> (n_chunks, B, S / n_chunks, ...)."""
    def chunked(a):
        b, s = a.shape[:2]
        return jnp.moveaxis(
            a.reshape(b, n_chunks, s // n_chunks, *a.shape[2:]), 1, 0)

    return tuple(chunked(a) for a in arrays)


def _seq_unchunk(a):
    """(n_chunks, B, C, ...) -> (B, n_chunks * C, ...)."""
    a = jnp.moveaxis(a, 0, 1)
    return a.reshape(a.shape[0], -1, *a.shape[3:])


def _chunk_ce(xc, tc, embd, inv_scale):
    """One sequence chunk through the head: ``(e, sumexp, nll)`` with
    ``e = exp(logits - rowmax)`` (B, C, V), its row sums and the
    per-token ``logsumexp - target logit``, all float32; the logits are
    the product times ``inv_scale``."""
    # (B, C, M) @ (M, V): f32 accumulation on bf16 operands, same
    # numerics as the unfused logits einsum
    logits = jnp.einsum("bcm,vm->bcv", xc, embd,
                        preferred_element_type=jnp.float32)
    if inv_scale != 1.0:
        logits = logits * np.float32(inv_scale)
    top = jnp.max(logits, axis=-1)
    e = jnp.exp(logits - top[..., None])
    sumexp = jnp.sum(e, axis=-1)
    tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
    return e, sumexp, (jnp.log(sumexp) + top) - tgt


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _mean_ce(x, embd, targets, weights, denom, n_chunks, inv_scale):
    """``sum(nll * weights) / denom`` over the sequence chunks."""
    def body(total, inp):
        xc, tc, wc = inp
        return total + jnp.sum(
            _chunk_ce(xc, tc, embd, inv_scale)[2] * wc), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            _seq_chunks(n_chunks, x, targets, weights))
    return total / denom


def _mean_ce_fwd(x, embd, targets, weights, denom, n_chunks, inv_scale):
    """The loss AND its gradients, each chunk's formed from the logits
    the loss was read from: ``(softmax - onehot) * weight / denom``
    against the head gives the chunk's dx, against the chunk of ``x``
    its share of the head's gradient, which the scan carries."""
    inv = 1.0 / denom

    def body(demb, inp):
        xc, tc, wc = inp
        # the chunk as a value of its own, sliced once: fused into the
        # two products that read it, the slice out of the stacked ``x``
        # costs each a slower tiling on the chip (3.2 ms of the head's
        # 85.5 at (4, 4096, 4096) x 32000; PERF.md section 6, PR 33)
        xc = jax.lax.optimization_barrier(xc)
        e, sumexp, nll = _chunk_ce(xc, tc, embd, inv_scale)
        scale = inv * wc
        if inv_scale != 1.0:
            # with respect to the product: the scaled logits' gradient
            # times the scale
            scale = scale * np.float32(inv_scale)
        onehot = jax.lax.broadcasted_iota(
            jnp.int32, e.shape, e.ndim - 1) == tc[..., None]
        dlogits = e * (scale / sumexp)[..., None] - jnp.where(
            onehot, scale[..., None], 0.0)
        # float32 dlogits against bf16 operands at the default
        # precision, as autodiff's transposes of the logits product are
        dxc = jax.lax.dot_general(
            dlogits, embd, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(xc.dtype)
        dembc = jax.lax.dot_general(
            dlogits, xc, (((0, 1), (0, 1)), ((), ())),
            preferred_element_type=jnp.float32).astype(embd.dtype)
        return demb + dembc, (dxc, nll * inv, jnp.sum(nll * wc))

    # last chunk first: the order in which autodiff's backward scan
    # summed the head's gradient in its (bf16) carry; the loss is
    # summed first chunk first, as the plain call above sums it
    demb, (dx, dweights, parts) = jax.lax.scan(
        body, jnp.zeros_like(embd),
        _seq_chunks(n_chunks, x, targets, weights), reverse=True)
    total = functools.reduce(jnp.add, parts)
    return total / denom, (
        _seq_unchunk(dx), demb, _seq_unchunk(dweights),
        -total * jax.lax.integer_pow(denom, -2))


def _mean_ce_bwd(n_chunks, inv_scale, res, g):
    # the step's cotangent is the literal 1, which the compiler drops:
    # no sweep over the head's gradient is spent on a scale
    dx, demb, dweights, ddenom = res
    return (dx * g.astype(dx.dtype), demb * g.astype(demb.dtype), None,
            dweights * g, ddenom * g)


_mean_ce.defvjp(_mean_ce_fwd, _mean_ce_bwd)


def chunked_lm_loss(x, emb, targets, n_chunks=8, weights=None,
                    logits_scaling=1.0):
    """Cross-entropy fused with the logits projection, chunked over the
    sequence so the full (B, S, V) logits tensor is never materialized.

    ``lm_loss(model.apply(...), targets)`` stores the f32 logits plus a
    f32 log-softmax — 2 * B*S*V*4 bytes of HBM (4.2 GB at B=4, S=4096,
    V=32k) that caps the trainable batch and adds two full HBM sweeps.
    Here each ``lax.scan`` step projects one sequence chunk, reduces it
    to per-token (logsumexp − target-logit) contributions, and drops
    the chunk logits.

    Under ``jax.grad`` the gradient is formed in the forward pass
    (a ``jax.custom_vjp``): the scan step that holds a chunk's logits
    also forms ``(softmax − onehot) * weight`` from them, the chunk's
    ``dx`` and its share of the head's gradient, so the head runs three
    vocabulary-sized products a step (logits, dx, d head) and no chunk
    is projected a second time; the backward pass only scales what the
    forward kept by the incoming cotangent.  The price: forward-mode
    differentiation (``jax.jvp`` / ``jacfwd``) and second derivatives
    of this loss are not offered (nothing in ``horovod_tpu`` takes
    either).  ``x``, ``emb`` and ``weights`` are differentiable.

    Exactly equals ``lm_loss`` in f32 (tests/test_models.py).

    Args:
      x: final hidden states (B, S, M) in the activation dtype
         (``model.apply(..., pre_logits=True)``).
      emb: the head (V, M) f32: the tied embedding or ``lm_head``.
      targets: (B, S) int32 target ids (already shifted).
      n_chunks: sequence chunks; S % n_chunks must be 0.
      weights: optional (B, S) f32 per-token weights — pass 0 for
        padding / the final position when feeding unshifted batches
        (``targets=roll(tokens)``, ``weights[:, -1]=0``); the mean is
        over the weight sum.
      logits_scaling: the logits are the projection divided by this
        (Granite's ``config.json``), in the loss and, inside the same
        rule, in the gradients it forms.
    """
    b, s, m = x.shape
    if s % n_chunks:
        raise ValueError(f"seq len {s} not divisible by n_chunks "
                         f"{n_chunks}")
    if weights is None:
        weights = jnp.ones((b, s), jnp.float32)
    with jax.named_scope("lm_head_ce"):
        denom = jnp.sum(weights)
        # all-padding batches (weight sum 0) yield loss 0, not 0/0 = NaN
        return _mean_ce(x, emb.astype(x.dtype), targets, weights,
                        jnp.where(denom > 0, denom, 1.0), n_chunks,
                        1.0 / logits_scaling)


def make_fused_lm_loss(model: "TransformerLM", n_chunks: int = 16,
                       with_state: bool = False):
    """``loss_fn(params, tokens)`` computing the next-token objective of
    ``lm_loss(model.apply(...)[:, :-1], tokens[:, 1:])`` via
    :func:`chunked_lm_loss` — targets rolled (not sliced, so S stays
    chunkable and sp-shard-aligned) with the final position weighted 0.
    The head's gradient is formed in the forward pass, by that
    function's own rule: ``jax.grad`` / ``value_and_grad`` of
    ``loss_fn`` are what it offers, forward-mode and second
    derivatives are not.

    The single definition of the fused objective, shared by
    ``parallel.make_lm_train_step(fused_ce=True)``, the pipelined step,
    ``chip_smoke.py`` and the benchmark so they cannot drift apart.

    ``model`` is a ``TransformerLM`` (flax) or any plain
    ``apply(params, tokens, pre_logits=True) -> (x, emb)`` callable
    (e.g. ``make_pipelined_lm_apply``'s).

    A model whose router is balanced by an auxiliary loss
    (``router_aux_loss_coef`` > 0) hands that loss's mean over its
    routed layers back beside the states, and the objective is the
    cross-entropy plus the coefficient times it.

    ``with_state`` (a flax model): ``loss_fn(params, state, tokens) ->
    (loss, new_state)`` for ``make_compiled_train_step(...,
    has_aux=True)``, where ``state`` is the model's other collections
    (``model.init(...)`` without ``"params"``: the routed layers'
    ``router_state``), which the step threads as it threads batch
    statistics."""
    if hasattr(model, "apply"):
        def pre(params, tokens):
            return model.apply({"params": params}, tokens,
                               pre_logits=True)
    else:
        def pre(params, tokens):
            return model(params, tokens, pre_logits=True)

    cfg = getattr(model, "cfg", None)
    looped = getattr(cfg, "total_ut_steps", 1) > 1

    aux_coef = getattr(cfg, "router_aux_loss_coef", 0.0) \
        if getattr(model, "routed_layers", 0) else 0.0

    def objective(x, emb, tokens):
        targets = jnp.roll(tokens, -1, axis=1)
        w = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
        def cross_entropy(x):
            # a plain callable (the pipelined apply) has no cfg
            return chunked_lm_loss(
                x, emb, targets, n_chunks=n_chunks, weights=w,
                logits_scaling=getattr(cfg, "logits_scaling", 1.0))

        if aux_coef:
            # a loss term born inside the scanned, rematerialised layers
            # (the routed layers' balance loss, their mean) came out
            # beside the states: it joins the cross-entropy here
            x, aux_loss = x
            return cross_entropy(x) + aux_coef * aux_loss
        if not looped:
            return cross_entropy(x)
        # a looped model: the expectation of the passes' cross-entropies
        # under each token's exit distribution, less the entropy bonus.
        # The distribution sums to 1, so the exits stacked on the batch
        # axis with the weights p * w go through the one chunked loss
        # with the one denominator sum(w)
        states, gates = x
        passes = states.shape[0]
        with jax.named_scope("exit_gate"):
            p, entropy = exit_distribution(gates)
            weighted = p * w
            bonus = jnp.sum(entropy * w) / jnp.sum(w)
            device_sums.add(LOOP_TOKENS_SUM,
                            jnp.sum(w).astype(jnp.int32))
            for t, mass in enumerate(jnp.sum(weighted, axis=(1, 2)), 1):
                device_sums.add_fraction(loop_exit_mass_sum(t), mass)
        expected = chunked_lm_loss(
            states.reshape((-1,) + states.shape[2:]), emb,
            jnp.tile(targets, (passes, 1)), n_chunks=n_chunks,
            weights=weighted.reshape(-1, w.shape[-1]),
            logits_scaling=cfg.logits_scaling)
        return expected - cfg.exit_entropy_coeff * bonus

    if with_state:
        def loss_fn(params, state, tokens):
            (x, emb), new_state = model.apply(
                {"params": params, **state}, tokens, pre_logits=True,
                mutable=list(state))
            return objective(x, emb, tokens), new_state
    else:
        def loss_fn(params, tokens):
            return objective(*pre(params, tokens), tokens)
    # what the model sums on the device, for the compiled step, and
    # what a step call of it counts on the host
    loss_fn.device_sums = tuple(getattr(model, "device_sums", ()))
    if looped:
        loss_fn.step_counts = {
            LOOP_LAYER_APPLICATIONS: cfg.n_layers * cfg.total_ut_steps}
    return loss_fn
