"""Kimi Delta Attention: a gated delta rule in attention's place.

A layer of kind ``"kda"`` (``TransformerConfig.layer_types``; the
``linear_attn_config`` of Kimi-Linear's published ``config.json``,
arXiv:2510.26692 section 3, ``fla``'s ``KimiDeltaAttention``) mixes
tokens through a (d_k, d_v) state a head that DECAYS by one rate a key
channel and is then corrected by a rank-one delta.  With ``x`` the
normed input, per head over its 128 channels::

    q = l2norm(silu(conv(x Wq)));  k = l2norm(silu(conv(x Wk)))
    v = silu(conv(x Wv))                   # causal depthwise, one each
    g = -exp(A_log_h) * softplus((x Wf_a) Wf_b + dt_bias)   # <= 0, float32
    beta = sigmoid(x Wb)                   # one a head
    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T (q_t / sqrt(d_k))
    out = (rmsnorm_head(o) * sigmoid((x Wg_a) Wg_b)) Wo

equivalently, a token: ``S <- exp(g_t)[:, None] * S; u = beta_t (v_t -
S^T k_t); S <- S + k_t u^T``.

``kda_chunked`` computes the recurrence in its CHUNKED form
(arXiv:2412.06464 section 3 with the decay a channel).  With ``G`` the
cumulative sum of ``g`` inside a chunk of C positions and ``S_0`` the
state the chunk starts from, the corrected values ``U`` (rows ``u_t``)
solve ``(I + A) U = beta (V - (K exp(G)) S_0)`` with the strictly lower
``A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)``; position i reads
``o_i = (q_i exp(G_i)) S_0 + sum_{j<=i} B_ij u_j`` with ``B_ij = sum_c
q_ic k_jc exp(G_ic - G_jc)``; and the chunk ends with ``S_C = exp(G_C)
S_0 + (K exp(G_C - G))^T U``.  So, batched over all the chunks at once:
the pairwise products ``A`` and ``B``, ``T = (I + A)^-1`` by block
forward substitution, ``W = T beta [V | K exp(G)]``, and the parts that
do not know the state, ``B W_v`` and ``q exp(G) - B W_k``; then a short
recurrence over the chunks carries the state; a last product reads it.
The whole is ONE ``custom_vjp`` that keeps its inputs and the states the
chunks start from and nothing else: its backward computes the chunks'
own parts again, runs the rule's own reverse recurrence over the chunks
from the kept states, and goes back through the chunks' arithmetic by
autodiff, a group of heads at a time forward and backward
(``GROUP_POSITIONS``), because the halving's temporaries over all the
heads of a step at once would not fit beside the model.

**Every exponent is a difference ``G_i - G_j <= 0`` formed before the
exp.**  ``k exp(-G)`` on its own overflows where a channel decays fast,
and the pairwise decay of a channel does not factor into a row times a
column about one point of the chunk.  It does about a point BETWEEN the
row and the column: the chunk's lower triangle is cut into its two
halves' off-diagonal square, then each half's, and so on down to single
positions (log2 C levels); in a square the rows lie after the columns,
and with ``r`` the columns' last position ``exp(G_i - G_j) = exp(G_i -
G_r) exp(G_r - G_j)``, both exponents <= 0.  All the levels are ONE
batched product over whole chunks (a level's factors zero outside its
rows and columns, what falls between two squares masked after), not
slices of them: the slicing and the concatenating cost the chip five
times the products (PERF.md section 6, PR 50).  The same halving builds
``T``: ``[[T1, 0], [-T2 A21 T1, T2]]`` is ``T - T A21 T`` over the whole
chunk.  A channel whose ``exp(G)`` underflows inside a chunk is then a
channel that forgets, not a NaN.

``G``, the exps and the inverse are float32 (the inverse's products at
``highest``); the other products take operands in the activation dtype
and accumulate in float32.  The chunk length (a power of two) changes
no result beyond rounding.  Plain XLA operations: a kernel pair is
ROADMAP's.

Scopes (docs/observability.md "The compiled step"): the module is named
``kda``; inside it ``in_proj`` (the eight projections of ``x``),
``conv``, ``delta`` (everything between the convolutions and the gated
norm), ``gate_norm``, ``out_proj``.
"""

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from .mamba import CausalConv, _a_log_init, _dt_bias_init

#: the sums the delta-rule layers make on the device, a step call
#: (``ops/device_sums.py``): the tokens and the chunks their scans
#: processed, over the layers (their ratio is the chunk length that ran)
KDA_DEVICE_SUMS = ("horovod_kda_tokens_total", "horovod_kda_chunks_total")

#: the names a scan's output and the states its chunks start from are
#: checkpointed under: every remat policy of a model with kda layers
#: keeps them (``transformer._with_remat``), so a replay runs no
#: recurrence over the chunks again
KEPT_OUTPUT = "kda_out"
KEPT_STATES = "kda_states"
KEPT = (KEPT_OUTPUT, KEPT_STATES)

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def _levels(length):
    """The halving of a chunk of ``length`` positions, as constants: for
    each level (squares of 1, 2, 4, ... positions) which positions are
    rows (the second half of their pair of squares), and which (row,
    column) pairs the level's squares hold, (levels, C) and (levels, C,
    C) bool."""
    at = np.arange(length)
    sizes = [1 << n for n in range(length.bit_length() - 1)]
    is_row = np.stack([(at // size) % 2 == 1 for size in sizes])
    pair = np.stack([at // (2 * size) for size in sizes])
    square = is_row[:, :, None] & ~is_row[:, None, :] \
        & (pair[:, :, None] == pair[:, None, :])
    return sizes, is_row, square


def _about(total, size):
    """(..., C, K) -> the same shape: at every position the value at
    the LAST position of the first half of its pair of ``size``-squares
    (the columns' last: rows lie after it, columns at or before it)."""
    shape = total.shape
    pairs = total.reshape(shape[:-2] + (shape[-2] // (2 * size), 2 * size,
                                        shape[-1]))
    return jnp.broadcast_to(pairs[..., size - 1:size, :],
                            pairs.shape).reshape(shape)


def _within_chunks(q, k, v, g, beta, dtype):
    """What a chunk computes without its starting state.  q, k, g:
    (..., C, K); v: (..., C, V); beta: (..., C); all float32.  Returns
    ``(w_v, w_k, k_end, q_read, out, decay)``: ``U = w_v - w_k S_0``,
    the state ends as ``decay[:, None] * S_0 + k_end^T U``, and the
    chunk's output is ``out + q_read S_0``."""
    length, width = v.shape[-2:]
    total = jnp.cumsum(g, axis=-2)
    eye = jnp.eye(length, dtype=_F32)
    # the diagonal: a position reads its own u through q_i . k_i
    reads = eye * jnp.sum(q * k, axis=-1)[..., None]
    inverse = eye
    if length > 1:
        # every level of the halving at once, a leading axis: a level's
        # squares as ONE product over the whole chunk, rows' factors
        # zero off the rows and columns' off the columns (the exponent
        # masked BEFORE the exp), and what falls outside the level's
        # squares, pairs of two different pairs of squares, masked after
        sizes, is_row, square = _levels(length)
        lead = (len(sizes),) + (1,) * (total.ndim - 2)
        is_row = is_row.reshape(lead + (length, 1))
        square = square.reshape(lead + (length, length))
        after = total - jnp.stack([_about(total, size) for size in sizes])
        to_rows = jnp.exp(jnp.where(is_row, after, -jnp.inf))
        cols = (k * jnp.exp(jnp.where(is_row, -jnp.inf, -after))).astype(dtype)
        rows = jnp.concatenate([k * to_rows, q * to_rows],
                               axis=-2).astype(dtype)
        pairs = jnp.einsum("...ic,...jc->...ij", rows, cols,
                           preferred_element_type=_F32)
        lower = jnp.where(square, pairs[..., :length, :], 0.0) \
            * beta[..., None]
        reads = reads + jnp.sum(
            jnp.where(square, pairs[..., length:, :], 0.0), axis=0)
        # (I + A)^-1 by block forward substitution: with the inverses of
        # a level's diagonal squares in hand, [[T1, 0], [-T2 A21 T1, T2]]
        # is T - T A21 T over the whole chunk
        inverse = eye - lower[0]
        for corner in lower[1:]:
            inverse = inverse - jnp.einsum(
                "...ij,...jk->...ik",
                jnp.einsum("...ij,...jk->...ik", inverse, corner,
                           precision=_HIGHEST),
                inverse, precision=_HIGHEST)

    decayed = jnp.exp(total)
    w = jnp.einsum("...ij,...jd->...id", inverse,
                   beta[..., None] * jnp.concatenate([v, k * decayed],
                                                     axis=-1),
                   precision=_HIGHEST)
    read = jnp.einsum("...ij,...jd->...id", reads.astype(dtype),
                      w.astype(dtype), preferred_element_type=_F32)
    last = total[..., -1:, :]
    return (w[..., :width], w[..., width:], k * jnp.exp(last - total),
            q * decayed - read[..., width:], read[..., :width],
            jnp.exp(last[..., 0, :]))


def _starts(w_v, w_k, k_end, decay):
    """The state each chunk STARTS from, (chunks, B, H, K, V) float32:
    the short recurrence over the chunks (axis 2 of the operands, the
    matrices in the activation dtype)."""
    dtype = w_v.dtype

    def carry_on(state, chunk_):
        w_v, w_k, k_end, decay = chunk_
        u = w_v.astype(_F32) - jnp.einsum(
            "bhck,bhkv->bhcv", w_k, state.astype(dtype),
            preferred_element_type=_F32)
        return decay[..., None] * state + jnp.einsum(
            "bhck,bhcv->bhkv", k_end, u.astype(dtype),
            preferred_element_type=_F32), state

    rows, heads, _, _, width = w_v.shape
    _, starts = jax.lax.scan(
        carry_on, jnp.zeros((rows, heads, w_k.shape[-1], width), _F32),
        tuple(jnp.moveaxis(t, 2, 0) for t in (w_v, w_k, k_end, decay)))
    return starts


def _across_backward(w_v, w_k, k_end, q_read, decay, starts, d_out):
    """The rule's own backward over the chunks: the gradient of the
    state a chunk ENDS with is carried from the last chunk to the first.
    Returns the gradients of the first five operands, float32."""
    dtype = w_v.dtype
    d_out = d_out.astype(dtype)

    def carry_back(d_end, chunk_):
        w_v, w_k, k_end, q_read, decay, start, d_out = chunk_
        low = d_end.astype(dtype)
        d_u = jnp.einsum("bhck,bhkv->bhcv", k_end, low,
                         preferred_element_type=_F32)
        u = w_v.astype(_F32) - jnp.einsum(
            "bhck,bhkv->bhcv", w_k, start, preferred_element_type=_F32)
        d_w_k = -jnp.einsum("bhcv,bhkv->bhck", d_u.astype(dtype), start,
                            preferred_element_type=_F32)
        d_k_end = jnp.einsum("bhcv,bhkv->bhck", u.astype(dtype), low,
                             preferred_element_type=_F32)
        d_decay = jnp.sum(start.astype(_F32) * d_end, axis=-1)
        d_start = jnp.einsum("bhck,bhcv->bhkv", q_read, d_out,
                             preferred_element_type=_F32) \
            + decay[..., None] * d_end \
            - jnp.einsum("bhck,bhcv->bhkv", w_k, d_u.astype(dtype),
                         preferred_element_type=_F32)
        return d_start, (d_u, d_w_k, d_k_end, d_decay)

    over_chunks = tuple(jnp.moveaxis(t, 2, 0)
                        for t in (w_v, w_k, k_end, q_read, decay))
    _, found = jax.lax.scan(
        carry_back, jnp.zeros(starts.shape[1:], _F32),
        over_chunks + (starts, jnp.moveaxis(d_out, 2, 0)), reverse=True)
    d_w_v, d_w_k, d_k_end, d_decay = (jnp.moveaxis(t, 0, 2) for t in found)
    d_q_read = jnp.einsum("bhncv,nbhkv->bhnck", d_out, starts,
                          preferred_element_type=_F32)
    return d_w_v, d_w_k, d_k_end, d_q_read, d_decay


#: a group of heads takes the rule at once: the chunked form's
#: temporaries (a score-shaped and a key-shaped tensor for every level of
#: the halving) are a few hundred bytes a position and a head, 14 GB over
#: the 2 x 8,192 x 32 of a step, so the heads go in groups of at most
#: this many (positions x heads) one after another, forward and backward
GROUP_POSITIONS = 1 << 16


def _group_size(rows, seq, heads, limit):
    """The most heads a group, a divisor of ``heads``, within ``limit``
    positions x heads (at least one)."""
    return max(h for h in range(1, heads + 1)
               if heads % h == 0 and (h == 1 or rows * seq * h <= limit))


def _by_group(t, length, each):
    """(B, S, H, ...) -> (groups, B, each, chunks, C, ...), float32; a
    row that ends inside a chunk filled up with zeros (positions of
    beta = 0 and g = 0 leave the state as it is)."""
    rows, seq, heads = t.shape[:3]
    t = jnp.pad(t.astype(_F32), ((0, 0), (0, -seq % length))
                + ((0, 0),) * (t.ndim - 2))
    t = t.reshape((rows, -1, length, heads // each, each) + t.shape[3:])
    return jnp.moveaxis(t, (3, 4), (0, 2))


def _prepared(q, k, v, g, beta, length, each):
    return tuple(_by_group(t, length, each) for t in (q, k, v, g, beta))


def _cast(parts, dtype):
    """``_within_chunks``'s matrices as the recurrence takes them."""
    w_v, w_k, k_end, q_read, out, decay = parts
    return tuple(t.astype(dtype) for t in (w_v, w_k, k_end, q_read)) \
        + (decay,)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, length, each):
    """The chunked rule over (B, S, H, ...) operands in chunks of
    ``length``, ``each`` heads at once: o (B, S', H, V) float32, S' the
    rows filled up to whole chunks."""
    return _rule_forward(q, k, v, g, beta, length, each)[0]


def _rule_forward(q, k, v, g, beta, length, each):
    dtype = q.dtype

    def group(operands):
        parts = _within_chunks(*operands, dtype)
        w_v, w_k, k_end, q_read, decay = _cast(parts, dtype)
        starts = _starts(w_v, w_k, k_end, decay).astype(dtype)
        return parts[4] + jnp.einsum("bhnck,nbhkv->bhncv", q_read, starts,
                                     preferred_element_type=_F32), starts

    out, starts = jax.lax.map(group, _prepared(q, k, v, g, beta, length,
                                               each))
    # named so that a checkpoint policy can keep them: with the states
    # and the mixer's output kept, a replay has no use for this function
    starts = checkpoint_name(starts, KEPT_STATES)
    # (groups, B, each, chunks, C, V) -> (B, S', H, V)
    out = jnp.moveaxis(out, (0, 2), (3, 4))
    out = out.reshape(out.shape[0], -1, out.shape[3] * each, out.shape[-1])
    return out, (q, k, v, g, beta, starts)


def _rule_backward(length, each, kept, d_out):
    """A group of heads at a time: what its chunks compute without
    their states again (nothing of it was kept), the rule's own reverse
    recurrence over the chunks from the kept states, and back through
    the chunks' own arithmetic."""
    q, k, v, g, beta, starts = kept
    dtype = q.dtype
    operands, to_inputs = jax.vjp(
        lambda *a: _prepared(*a, length, each), q, k, v, g, beta)

    def group(args):
        operands, starts, d_out = args
        parts, to_operands = jax.vjp(
            lambda *a: _within_chunks(*a, dtype), *operands)
        d_w_v, d_w_k, d_k_end, d_q_read, d_decay = _across_backward(
            *_cast(parts, dtype), starts, d_out)
        return to_operands((d_w_v, d_w_k, d_k_end, d_q_read, d_out,
                            d_decay))

    return to_inputs(jax.lax.map(
        group, (operands, starts, _by_group(d_out, length, each))))


_rule.defvjp(_rule_forward, _rule_backward)


def kda_chunked(q, k, v, g, beta, *, chunk):
    """``o_t = S_t^T q_t`` with ``S_t = (I - beta_t k_t k_t^T)
    diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T`` and ``S`` zero before
    each row's first position.

    q, k: (B, S, H, K) and v: (B, S, H, V) in the activation dtype (q
    scaled already); g: (B, S, H, K) float32, <= 0; beta: (B, S, H)
    float32.  Returns ``(o, chunks)``: o (B, S, H, V) float32 and the
    number of chunks a row took."""
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(
            f"kda_chunk_size must be a power of two, got {chunk}")
    rows, seq, heads, _ = q.shape
    length = chunk
    while length // 2 >= seq:
        length //= 2
    each = _group_size(rows, seq, heads, GROUP_POSITIONS)
    out = _rule(q, k, v, g.astype(_F32), beta.astype(_F32), length, each)
    return out[:, :seq], -(-seq // length)


def l2norm(x, eps=1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis, float32."""
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


class GatedHeadNorm(nn.Module):
    """``RMSNorm_w(o) * sigmoid(gate)`` over each head's own width
    (one learned scale of that width, shared by the heads), float32."""
    dtype: Any
    eps: float

    @nn.compact
    def __call__(self, o, gate):
        scale = self.param("scale", nn.initializers.ones, (o.shape[-1],),
                           _F32)
        o = o.astype(_F32)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + self.eps)
        return (o * scale * nn.sigmoid(gate.astype(_F32))).astype(self.dtype)


class KDAMixer(nn.Module):
    """(B, S, d_model) -> ``(out (B, S, d_model), counts)``; ``counts``
    int32 (2,) are the tokens and the chunks the scan processed
    (``KDA_DEVICE_SUMS``)."""
    cfg: Any      # a TransformerConfig
    dot_general: Any = None    # the projections' product (None: flax's
    # own), as ``transformer.Attention``'s

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        heads, width = cfg.kda_n_heads, cfg.kda_d_head
        if (heads or 0) < 1 or (width or 0) < 1:
            raise ValueError(
                "a kda layer needs kda_n_heads and kda_d_head, got "
                f"{heads} and {width}")
        inner = heads * width
        rows, seq, _ = h.shape
        chunk = cfg.kda_chunk_size or 64

        def dense(feats, name):
            return nn.Dense(feats, use_bias=False, dtype=cfg.dtype,
                            param_dtype=_F32, dot_general=self.dot_general,
                            name=name)

        with jax.named_scope("in_proj"):
            q, k, v = (dense(inner, name)(h) for name in ("wq", "wk", "wv"))
            # the two low-rank gates, their rank a head's width
            decay = dense(inner, "f_b")(dense(width, "f_a")(h))
            gate = dense(inner, "g_b")(dense(width, "g_a")(h))
            beta = dense(heads, "b_proj")(h)
        with jax.named_scope("conv"):
            q, k, v = (CausalConv(cfg.kda_d_conv or 4, cfg.dtype,
                                  use_bias=False, name=name)(t)
                       for name, t in (("conv_q", q), ("conv_k", k),
                                       ("conv_v", v)))
        dt_bias = self.param("dt_bias", _dt_bias_init, (inner,), _F32)
        a_log = self.param("A_log", _a_log_init, (heads,), _F32)
        by_head = (rows, seq, heads, width)
        with jax.named_scope("delta"):
            q = (l2norm(q.reshape(by_head)) / np.sqrt(width)).astype(cfg.dtype)
            k = l2norm(k.reshape(by_head)).astype(cfg.dtype)
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                decay.astype(_F32) + dt_bias).reshape(by_head)
            o, chunks = kda_chunked(q, k, v.reshape(by_head), g,
                                    nn.sigmoid(beta.astype(_F32)),
                                    chunk=chunk)
            o = checkpoint_name(o.astype(cfg.dtype), KEPT_OUTPUT)
        o = GatedHeadNorm(cfg.dtype, cfg.rms_norm_eps, name="gate_norm")(
            o, gate.reshape(by_head))
        return dense(cfg.d_model, "out_proj")(o.reshape(rows, seq, inner)), \
            jnp.array([rows * seq, rows * chunks], jnp.int32)
