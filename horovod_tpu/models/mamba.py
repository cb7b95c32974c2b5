"""The Mamba-2 mixer: a state-space layer in attention's place.

A layer of kind ``"mamba"`` (``TransformerConfig.layer_types``, the key
of published ``config.json`` files such as Granite-4.0-H's
``granitemoehybrid``) mixes tokens with no attention at all
(arXiv:2405.21060, ``transformers``' ``modeling_granitemoehybrid.py``)::

    [z | xBC | dt] = h W_in          (d_inner | d_inner + 2 G N | H)
    xBC = silu(causal_depthwise_conv(xBC) + b)        # width d_conv
    [x | B | C] = xBC                # x: H heads of P; B, C: G groups of N
    dt = softplus(dt + dt_bias);  A = -exp(A_log)     # float32, a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        # (P, N) a head
    y_t = S_t C_t + D x_t
    out = RMSNorm_w(y * silu(z)) W_out                # over all d_inner

The recurrence over the positions is computed in its CHUNKED form
("state-space duality"): inside a chunk of ``chunk`` positions position
``l`` reads position ``s <= l`` through ``(C_l . B_s) exp(sum_{s<r<=l}
dt_r A) dt_s x_s``, a masked product as attention's; each chunk's
contribution to the state is one product; a short recurrence over the
chunks carries the state across their boundaries; a last product reads
the carried state.  Every cumulative sum and decay is float32 and every
exponent is <= 0; the products take operands in the activation dtype
and accumulate in float32.  The chunk length changes no result beyond
rounding.

Two implementations of that form, chosen by shape and dtype alone
(``ops/ssd_kernels.kernel_takes``): where a chunk, the state and a block
of heads fill whole tiles, a pair of Pallas kernels under one
``custom_vjp`` (``ssd_fwd`` sweeps a row's chunks with the state in VMEM
scratch and the decay tile never in HBM; ``ssd_bwd`` sweeps them in
reverse: the backward is the rule's own, not autodiff's); elsewhere (the
CPU tests' tiny widths) ``ssd_chunked`` below, plain XLA operations with
autodiff's backward, which is also what the kernels are tested against.

Scopes (docs/observability.md "The compiled step"): the module is named
``mamba``; inside it ``in_proj``, ``conv``, ``ssd`` (everything between
the convolution and the gated norm), ``gate_norm``, ``out_proj``.
"""

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..ops import ssd_kernels

#: the sums the state-space layers make on the device, a step call
#: (``ops/device_sums.py``): the tokens and the chunks their scans
#: processed, over the layers (their ratio is the chunk length that
#: ran), and the chunks of those that went through the kernel pair
SSM_DEVICE_SUMS = ("horovod_ssm_tokens_total", "horovod_ssm_chunks_total",
                   "horovod_ssm_kernel_chunks_total")

#: the names a scan's output and (where the kernel pair runs) the states
#: its chunks start from are checkpointed under: every remat policy of a
#: model with mamba layers keeps them (``transformer._with_remat``)
KEPT_OUTPUT = "ssd_out"
KEPT = (KEPT_OUTPUT, ssd_kernels.KEPT_STATES)


def ssd_chunked(x, dt, a, b, c, *, chunk):
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t
    B_t^T`` and ``S`` zero before each row's first position.

    x: (B, S, H, P) and b, c: (B, S, G, N) in the activation dtype
    (head ``h`` reads group ``h // (H / G)``); dt: (B, S, H) float32,
    positive; a: (H,) float32, negative.  Returns ``(y, chunks)``: y
    (B, S, H, P) float32 and the number of chunks a row took."""
    rows, seq, heads, width = x.shape
    groups, state = b.shape[-2:]
    each = heads // groups
    length = min(chunk, seq)
    # a row that ends inside a chunk is filled up with positions of
    # dt = 0: they leave the state as it is and add nothing to it
    fill = -seq % length
    if fill:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, fill)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    chunks = (seq + fill) // length
    dtype = x.dtype
    f32 = jnp.float32

    x = x.reshape(rows, chunks, length, groups, each, width)
    dt = dt.reshape(rows, chunks, length, groups, each)
    b = b.reshape(rows, chunks, length, groups, state)
    c = c.reshape(rows, chunks, length, groups, state)
    # log decay up to and with each position of its chunk, the positions
    # last: (rows, chunks, groups, each, length)
    cum = jnp.cumsum(jnp.moveaxis(dt, 2, -1) * a.reshape(groups, each, 1),
                     axis=-1)

    def by_position(t):
        """(..., groups, each, length) -> (..., length, groups, each, 1)"""
        return jnp.moveaxis(t, -1, 2)[..., None]

    # inside a chunk: position l reads s <= l through (C_l . B_s) times
    # the decay over (s, l]; the exponent is masked BEFORE the exp
    causal = jnp.tril(jnp.ones((length, length), bool))
    decay = jnp.exp(jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    scores = jnp.einsum("bclgn,bcsgn->bcgls", c, b,
                        preferred_element_type=f32)
    xdt = x.astype(f32) * dt[..., None]
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp",
                   (scores[:, :, :, None] * decay).astype(dtype),
                   xdt.astype(dtype), preferred_element_type=f32)

    if chunks > 1:
        # what each chunk adds to the state by its end ...
        to_end = jnp.exp(cum[..., -1:] - cum)
        added = jnp.einsum(
            "bcsgn,bcsgrp->bcgrpn", b,
            (xdt * by_position(to_end)).astype(dtype),
            preferred_element_type=f32)

        # ... the short recurrence over the chunks: the state each
        # STARTS from, float32 ...
        def carry_on(start, chunk_):
            kept, plus = chunk_
            return kept[..., None, None] * start + plus, start

        _, starts = jax.lax.scan(
            carry_on, jnp.zeros_like(added[:, 0]),
            (jnp.moveaxis(jnp.exp(cum[..., -1]), 1, 0),
             jnp.moveaxis(added, 1, 0)))
        # ... and what position l reads of it, decayed over the chunk's
        # positions up to and with l
        y = y + jnp.einsum(
            "bclgn,bcgrpn->bclgrp", c,
            jnp.moveaxis(starts, 0, 1).astype(dtype),
            preferred_element_type=f32) * by_position(jnp.exp(cum))
    y = y.reshape(rows, chunks * length, heads, width)
    return y[:, :seq], chunks


def _uniform(bound):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    """A uniform in [1, 16] (Mamba-2's ``A_init_range``)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """dt log-uniform in [0.001, 0.1], floor 1e-4, through the inverse
    of the softplus (Mamba-2's ``dt_min`` / ``dt_max`` /
    ``dt_init_floor``)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, np.log(1e-3),
                                    np.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


class CausalConv(nn.Module):
    """``silu(causal depthwise convolution + bias)`` over the positions
    of (B, S, C): position t reads t - width + 1 .. t of its own
    channel, zeros before the row's start.  ``use_bias`` off: no bias
    (models/kda.py's three)."""
    width: int
    dtype: Any
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        # torch's Conv1d default: uniform in +-1 / sqrt(fan_in = width)
        init = _uniform(1.0 / np.sqrt(self.width))
        kernel = self.param("kernel", init, (self.width, x.shape[-1]),
                            jnp.float32)
        seq = x.shape[1]
        back = jnp.pad(x, ((0, 0), (self.width - 1, 0), (0, 0)))
        y = sum(back[:, k:k + seq].astype(jnp.float32) * kernel[k]
                for k in range(self.width))
        if self.use_bias:
            y = y + self.param("bias", init, (x.shape[-1],), jnp.float32)
        return nn.silu(y).astype(self.dtype)


class GatedRMSNorm(nn.Module):
    """``RMSNorm_w(y * silu(z))`` over the whole last axis, float32."""
    dtype: Any
    eps: float

    @nn.compact
    def __call__(self, y, z):
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],),
                           jnp.float32)
        g = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + self.eps)
        return (g * scale).astype(self.dtype)


class Mamba2Mixer(nn.Module):
    """(B, S, d_model) -> ``(out (B, S, d_model), counts)``; ``counts``
    int32 (3,) are the tokens and the chunks the scan processed and the
    chunks of those the kernel pair took (``SSM_DEVICE_SUMS``)."""
    cfg: Any      # a TransformerConfig
    dot_general: Any = None    # the two projections' product (None:
    # flax's own), as ``transformer.Attention``'s

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        heads, width = cfg.mamba_n_heads, cfg.mamba_d_head
        groups, state = cfg.mamba_n_groups, cfg.mamba_d_state
        if heads < 1 or groups < 1 or heads % groups:
            raise ValueError(
                f"a mamba layer needs mamba_n_heads ({heads}) a multiple "
                f"of mamba_n_groups ({groups})")
        inner, bc = heads * width, groups * state
        rows, seq, _ = h.shape

        def dense(feats, name):
            return nn.Dense(feats, use_bias=False, dtype=cfg.dtype,
                            param_dtype=jnp.float32,
                            dot_general=self.dot_general, name=name)

        z, xbc, dt = jnp.split(dense(2 * inner + 2 * bc + heads,
                                     "in_proj")(h),
                               (inner, 2 * inner + 2 * bc), axis=-1)
        xbc = CausalConv(cfg.mamba_d_conv, cfg.dtype, name="conv")(xbc)
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,), jnp.float32)
        a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
        with jax.named_scope("ssd"):
            x, b, c = jnp.split(xbc, (inner, inner + bc), axis=-1)
            x = x.reshape(rows, seq, heads, width)
            b = b.reshape(rows, seq, groups, state)
            c = c.reshape(rows, seq, groups, state)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            kernels = ssd_kernels.kernel_takes(
                x.shape, b.shape, cfg.mamba_chunk_size, cfg.dtype)
            if kernels:
                y, chunks = ssd_kernels.ssd_scan(
                    x, dt, -jnp.exp(a_log), b, c, skip,
                    chunk=cfg.mamba_chunk_size)
            else:
                y, chunks = ssd_chunked(x, dt, -jnp.exp(a_log), b, c,
                                        chunk=cfg.mamba_chunk_size)
                y = y + skip[:, None] * x.astype(jnp.float32)
            y = checkpoint_name(y.reshape(rows, seq, inner).astype(cfg.dtype),
                                KEPT_OUTPUT)
        y = GatedRMSNorm(cfg.dtype, cfg.rms_norm_eps, name="gate_norm")(y, z)
        return dense(cfg.d_model, "out_proj")(y), jnp.array(
            [rows * seq, rows * chunks, rows * chunks * kernels], jnp.int32)
