"""Pallas TPU kernels for the hot ops.

The reference's custom device kernels are CUDA
(``horovod/common/ops/cuda/cuda_kernels.cu``: batched memcpy + fused
scale).  The TPU equivalents that XLA does NOT already fuse well:

* :func:`fused_scale_cast` — one VMEM pass for the eager staging
  path's pre/post scale + dtype cast (bf16 wire format), instead of
  two XLA ops with an HBM round-trip between them.
* :func:`flash_attention` — blockwise causal attention that never
  materializes the (S, S) score matrix: streaming softmax in VMEM,
  O(S) HBM traffic; two kernels, the forward and ONE backward.  Used
  by the single-chip fast path; the
  sequence-parallel path composes the same math with ``ppermute``
  (parallel/ring_attention.py).
* :func:`quantize_blockwise` / :func:`dequantize_blockwise` — the
  block-scaled int8 wire codec (ops/quantize.py semantics) as ONE
  fused VMEM pass each: absmax, bf16 scale, round/clip and the int8
  store happen without re-reading the block from HBM (XLA would split
  the absmax reduction and the rescale into two passes).
  :func:`fake_quantize_blockwise` composes them under a custom VJP
  whose backward is the identity — gradients are exact with respect
  to the DEQUANTIZED value (straight-through), so a training step that
  fake-quantizes its gradient wire differentiates cleanly.

Every entry takes ``interpret=``; left at ``None`` it follows the
platform the process computes on (:func:`default_interpret`): Mosaic
on a TPU, the Pallas interpreter elsewhere (the CPU tests).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def default_interpret():
    """``interpret=`` for a kernel whose caller left it ``None``:
    False (compile to Mosaic) where the process's default backend —
    the platform its jitted programs run on — is a TPU, True
    elsewhere.  A backend that cannot start raises here: a process
    that failed to get its chip must not drop to the interpreter and
    carry on."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# fused scale + cast

def _scale_cast_kernel(x_ref, o_ref, *, factor, out_dtype):
    x = x_ref[:].astype(jnp.float32) * np.float32(factor)
    o_ref[:] = x.astype(out_dtype)


def fused_scale_cast(x, factor, out_dtype=None, *, block=4096,
                     interpret=None):
    """``(x * factor).astype(out_dtype)`` in one VMEM pass (reference
    ScaleBufferCudaImpl, cuda_kernels.cu half2-vectorized scale)."""
    out_dtype = out_dtype or x.dtype
    if interpret is None:
        interpret = default_interpret()
    n = x.size
    flat = x.reshape(-1)
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    grid = flat.size // block
    out = pl.pallas_call(
        functools.partial(_scale_cast_kernel,
                          factor=float(factor),
                          out_dtype=out_dtype),
        out_shape=jax.ShapeDtypeStruct(flat.shape, out_dtype),
        grid=(grid,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        interpret=interpret,
    )(flat)
    return out[:n].reshape(x.shape)


# ---------------------------------------------------------------------------
# block-scaled int8 wire codec (quantized collectives)

from .quantize import BLOCK as _QBLOCK  # noqa: E402  (shared wire constant)

# scale-blocks handled per program instance: 128 scales x 256 elements
# = 32768 elements/program — the f32 view is 128 KiB of VMEM, the int8
# output tile (128, 256) satisfies the (32, 128) int8 tiling rule and
# the (1, 128) scale row satisfies the lane-width rule.
_QROWS = 128


def _quantize_kernel(x_ref, q_ref, s_ref):
    x = x_ref[:].astype(jnp.float32)                   # (_QROWS, BLOCK)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    # materialize the scale in bf16 BEFORE dividing so q * bf16(scale)
    # decodes exactly what was encoded (ops/quantize.py contract)
    scale = (absmax / np.float32(127.0)) \
        .astype(jnp.bfloat16).astype(jnp.float32)
    safe = jnp.where(scale > 0, scale, np.float32(1.0))
    q_ref[:] = jnp.clip(jnp.round(x / safe), -127, 127).astype(jnp.int8)
    s_ref[:] = scale.reshape(1, _QROWS)


def _dequantize_kernel(q_ref, s_ref, o_ref):
    x = q_ref[:].astype(jnp.float32) * \
        s_ref[:].reshape(_QROWS, 1)
    o_ref[:] = x.astype(o_ref.dtype)


def _pad_to_rows(flat, block_elems):
    n = flat.shape[0]
    nb = -(-max(n, 1) // block_elems)
    rows = -(-nb // _QROWS) * _QROWS
    pad = rows * block_elems - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, rows


def quantize_blockwise(x, *, interpret=None):
    """Flat float vector -> (q int8, scales f32), both padded to a
    ``_QROWS``-scale-block multiple (zeros encode as zeros; callers
    slice with the true length).  Same semantics as
    quantize.np_quantize_blockwise / quantize_blockwise_xla."""
    if interpret is None:
        interpret = default_interpret()
    flat, rows = _pad_to_rows(x.reshape(-1), _QBLOCK)
    xb = flat.reshape(rows, _QBLOCK)
    q, s = pl.pallas_call(
        _quantize_kernel,
        out_shape=(jax.ShapeDtypeStruct((rows, _QBLOCK), jnp.int8),
                   jax.ShapeDtypeStruct((1, rows), jnp.float32)),
        grid=(rows // _QROWS,),
        in_specs=[pl.BlockSpec((_QROWS, _QBLOCK), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((_QROWS, _QBLOCK), lambda i: (i, 0)),
                   pl.BlockSpec((1, _QROWS), lambda i: (0, i))),
        interpret=interpret,
    )(xb)
    return q.reshape(-1), s.reshape(-1)


def dequantize_blockwise(q, scales, n, out_dtype=jnp.float32, *,
                         interpret=None):
    """Inverse pass: (q, scales) from quantize_blockwise -> flat (n,)
    array of ``out_dtype``."""
    if interpret is None:
        interpret = default_interpret()
    rows = scales.shape[0]
    out = pl.pallas_call(
        _dequantize_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _QBLOCK), out_dtype),
        grid=(rows // _QROWS,),
        in_specs=[pl.BlockSpec((_QROWS, _QBLOCK), lambda i: (i, 0)),
                  pl.BlockSpec((1, _QROWS), lambda i: (0, i))],
        out_specs=pl.BlockSpec((_QROWS, _QBLOCK), lambda i: (i, 0)),
        interpret=interpret,
    )(q.reshape(rows, _QBLOCK), scales.reshape(1, rows))
    return out.reshape(-1)[:n]


@jax.custom_vjp
def fake_quantize_blockwise(x):
    """Quant->dequant roundtrip, any shape, same dtype — the value the
    quantized wire actually delivers.  Backward is the identity: the
    VJP is exact w.r.t. the dequantized value (straight-through), so
    ``grad(loss(fake_quantize(g)))`` equals ``grad(loss(g))`` evaluated
    at the dequantized point instead of the useless a.e.-zero
    derivative of round()."""
    q, s = quantize_blockwise(x.reshape(-1))
    return dequantize_blockwise(q, s, x.size, x.dtype).reshape(x.shape)


def _fq_fwd(x):
    return fake_quantize_blockwise(x), None


def _fq_bwd(_, g):
    return (g,)


fake_quantize_blockwise.defvjp(_fq_fwd, _fq_bwd)


# ---------------------------------------------------------------------------
# block-scaled int4 wire codec (cross-hop / DCN wire format)

def _nibble_weights(even, odd):
    """(BLOCK, BLOCK // 2) bf16 matrix W with W[2j, j] = ``even`` and
    W[2j + 1, j] = ``odd``.  ``codes @ W(1, 16)`` is the
    np_pack_nibbles layout (even index low nibble).  Mosaic cannot
    de-interleave lanes (a reshape to (..., 2) is refused), so the
    pack and unpack ride the otherwise idle MXU; small integers are
    exact in bf16 operands with f32 accumulation."""
    r = jax.lax.broadcasted_iota(jnp.int32, (_QBLOCK, _QBLOCK // 2), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (_QBLOCK, _QBLOCK // 2), 1)
    w = jnp.where(r == 2 * c, np.float32(even),
                  jnp.where(r == 2 * c + 1, np.float32(odd),
                            np.float32(0.0)))
    return w.astype(jnp.bfloat16)


def _quantize_int4_kernel(x_ref, q_ref, s_ref):
    x = x_ref[:].astype(jnp.float32)                   # (_QROWS, BLOCK)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    # bf16-materialized scale BEFORE the division, exactly like the
    # int8 kernel (ops/quantize.py contract; qmax = 7)
    scale = (absmax / np.float32(7.0)) \
        .astype(jnp.bfloat16).astype(jnp.float32)
    safe = jnp.where(scale > 0, scale, np.float32(1.0))
    q = jnp.clip(jnp.round(x / safe), -7, 7)
    # biased-nibble pack fused into the same VMEM pass:
    # (q_even + 8) | (q_odd + 8) << 4 == q_even + 16 * q_odd + 136
    packed = jnp.dot(q.astype(jnp.bfloat16), _nibble_weights(1, 16),
                     preferred_element_type=jnp.float32)
    q_ref[:] = (packed + np.float32(136.0)) \
        .astype(jnp.int32).astype(jnp.uint8)
    s_ref[:] = scale.reshape(1, _QROWS)


def _dequantize_int4_kernel(q_ref, s_ref, o_ref):
    p = q_ref[:].astype(jnp.int32)                # (_QROWS, BLOCK//2)
    lo = ((p & 0x0F) - 8).astype(jnp.bfloat16)
    hi = ((p >> 4) - 8).astype(jnp.bfloat16)
    # interleave back to (_QROWS, BLOCK): code[2j] = lo[j],
    # code[2j + 1] = hi[j] — the transposed selection products
    contract = (((1,), (1,)), ((), ()))
    q = jax.lax.dot_general(lo, _nibble_weights(1, 0), contract,
                            preferred_element_type=jnp.float32) + \
        jax.lax.dot_general(hi, _nibble_weights(0, 1), contract,
                            preferred_element_type=jnp.float32)
    o_ref[:] = (q * s_ref[:].reshape(_QROWS, 1)).astype(o_ref.dtype)


def quantize_blockwise_int4(x, *, interpret=None):
    """Flat float vector -> (packed uint8, scales f32), both padded to
    a ``_QROWS``-scale-block multiple.  One fused VMEM pass: absmax,
    bf16 scale, round/clip AND the nibble pack happen without
    re-reading the block from HBM.  Same semantics as
    quantize.np_quantize_blockwise_int4 / quantize_blockwise_int4_xla."""
    if interpret is None:
        interpret = default_interpret()
    flat, rows = _pad_to_rows(x.reshape(-1), _QBLOCK)
    xb = flat.reshape(rows, _QBLOCK)
    q, s = pl.pallas_call(
        _quantize_int4_kernel,
        out_shape=(jax.ShapeDtypeStruct((rows, _QBLOCK // 2),
                                        jnp.uint8),
                   jax.ShapeDtypeStruct((1, rows), jnp.float32)),
        grid=(rows // _QROWS,),
        in_specs=[pl.BlockSpec((_QROWS, _QBLOCK), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((_QROWS, _QBLOCK // 2),
                                lambda i: (i, 0)),
                   pl.BlockSpec((1, _QROWS), lambda i: (0, i))),
        interpret=interpret,
    )(xb)
    return q.reshape(-1), s.reshape(-1)


def dequantize_blockwise_int4(q, scales, n, out_dtype=jnp.float32, *,
                              interpret=None):
    """Inverse pass: (packed, scales) from quantize_blockwise_int4 ->
    flat (n,) array of ``out_dtype`` (unpack fused with the rescale)."""
    if interpret is None:
        interpret = default_interpret()
    rows = scales.shape[0]
    out = pl.pallas_call(
        _dequantize_int4_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _QBLOCK), out_dtype),
        grid=(rows // _QROWS,),
        in_specs=[pl.BlockSpec((_QROWS, _QBLOCK // 2),
                               lambda i: (i, 0)),
                  pl.BlockSpec((1, _QROWS), lambda i: (0, i))],
        out_specs=pl.BlockSpec((_QROWS, _QBLOCK), lambda i: (i, 0)),
        interpret=interpret,
    )(q.reshape(rows, _QBLOCK // 2), scales.reshape(1, rows))
    return out.reshape(-1)[:n]


@jax.custom_vjp
def fake_quantize_blockwise_int4(x):
    """int4 quant->dequant roundtrip, any shape, same dtype, with the
    same straight-through backward as :func:`fake_quantize_blockwise`
    — gradients are exact w.r.t. the dequantized value, so training
    through the int4 wire differentiates cleanly."""
    q, s = quantize_blockwise_int4(x.reshape(-1))
    return dequantize_blockwise_int4(q, s, x.size, x.dtype) \
        .reshape(x.shape)


def _fq4_fwd(x):
    return fake_quantize_blockwise_int4(x), None


def _fq4_bwd(_, g):
    return (g,)


fake_quantize_blockwise_int4.defvjp(_fq4_fwd, _fq4_bwd)


# ---------------------------------------------------------------------------
# flash attention (causal, forward)

# what a masked score is set to: BELOW the _NEG_INF a query's running
# maximum starts from, so exp(masked - maximum) is 0 in float32 even for
# a query that has met no key yet (a window that ends inside a key
# block leaves some queries' first block wholly masked), and `l` needs
# no second mask after the exp
_MASKED = 2 * _NEG_INF


def _band(qi, block_q, block_k, window):
    """The key blocks ``[first_kb, num_kb)`` query block ``qi`` meets, in
    both kernels.  Causal: up to the block that holds the LAST row's
    position (block_q may exceed block_k); a sliding window also skips
    blocks entirely BEFORE the first row's window start."""
    num_kb = ((qi + 1) * block_q - 1) // block_k + 1
    first_kb = 0
    if window is not None:
        # qi is a traced grid index — stay in jnp
        first_kb = jnp.maximum(0, qi * block_q - window + 1) // block_k
    return first_kb, num_kb


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k,
                  scale, window=None):
    """The forward for one query block: an online softmax over the key
    blocks inside the band.

    q_ref: (1, block_q, D); k_ref / v_ref: (1, S, D).  Matmuls run in
    the INPUT dtype with f32 accumulation: bf16 activations hit the
    MXU's fast path (f32 operands would halve+ its rate) while f32
    inputs keep exact reference numerics.  All softmax math is f32; the
    1/sqrt(D) scale is applied to the f32 scores, not to q, so no
    precision is lost to a low-precision pre-multiply.

    The scores are held TRANSPOSED, (block_k, block_q), as the backward
    holds them: a query's maximum and sum run down the key axis
    (elementwise across vregs, then one fold of 8 sublanes) and `m`,
    `l`, `alpha` are (1, block_q) rows of a few vregs that broadcast
    along sublanes as they lie; held (block_q, 1) each was 64 sparse
    vregs, and dividing by `l`, its log and `lse`'s relayout cost a
    grid step about as much as a block pair.  The accumulator is o^T,
    (D, block_q), transposed ONCE at the store; PV takes v's block as
    the transposed operand.

    Two key blocks an iteration, BOTH QK^T products before either
    softmax: inside one pair QK^T -> maximum -> exp -> PV is a chain
    (every key's score is needed before the first exp), and the
    compiler overlaps nothing across loop iterations, so the second
    pair's product is what the MXU does while the VPU works through the
    first pair's softmax.  The sums stay in key-block order."""
    block_q = q_ref.shape[1]
    D = q_ref.shape[2]
    qi = pl.program_id(1)
    q = q_ref[0]
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 1)
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)

    def scores(kb):
        cols = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        st = jax.lax.dot_general(                     # (bk, bq) f32
            k_ref[0, cols, :], q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * np.float32(scale)
        return st, v_ref[0, cols, :]

    def accumulate(kb, st, v, carry):
        ot, m, l = carry
        k_pos = kb * block_k + k_iota
        mask = q_pos >= k_pos
        if window is not None:
            mask = mask & (q_pos - k_pos < window)
        st = jnp.where(mask, st, np.float32(_MASKED))
        m_new = jnp.maximum(m, jnp.max(st, axis=0, keepdims=True))
        alpha = jnp.exp(m - m_new)
        pt = jnp.exp(st - m_new)
        l_new = l * alpha + jnp.sum(pt, axis=0, keepdims=True)
        ot_new = ot * alpha + jax.lax.dot_general(    # (D, bq) f32
            v, pt.astype(v.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return ot_new, m_new, l_new

    first_kb, num_kb = _band(qi, block_q, block_k, window)

    def two(i, carry):
        kb = first_kb + 2 * i
        st_a, v_a = scores(kb)
        st_b, v_b = scores(kb + 1)
        carry = accumulate(kb, st_a, v_a, carry)
        return accumulate(kb + 1, st_b, v_b, carry)

    def one(kb, carry):
        return accumulate(kb, *scores(kb), carry)

    twos = (num_kb - first_kb) // 2
    carry = (jnp.zeros((D, block_q), jnp.float32),
             jnp.full((1, block_q), _NEG_INF, jnp.float32),
             jnp.zeros((1, block_q), jnp.float32))
    carry = jax.lax.fori_loop(0, twos, two, carry)
    # a band of an odd number of blocks: the last one alone
    ot, m, l = jax.lax.fori_loop(first_kb + 2 * twos, num_kb, one, carry)
    l = jnp.maximum(l, np.float32(1e-30))
    o_ref[0] = (ot / l).T.astype(o_ref.dtype)
    # logsumexp per row, consumed by the backward kernel; stored as
    # (BH, 1, S) so TPU block shapes satisfy the (8, 128) tiling rule
    lse_ref[0] = m + jnp.log(l)


def _flash_bwd_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                      dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, block_k,
                      scale, window=None):
    """The whole backward for one query block: loop over the key blocks
    inside the band, recompute p from (q, k, lse) ONCE per block pair
    and feed all three gradients from it (5 matmuls a pair).  dq of
    this query block rides the loop's carry; dk / dv of every key block
    are summed into ``dk_acc`` / ``dv_acc``, float32 (S, D) buffers
    that stay in VMEM across the query blocks of one (batch, head):
    zeroed at the first query block, scaled, cast and written out at
    the last.  So the query-block grid axis must run in order
    (``"arbitrary"``).

    The scores are held TRANSPOSED, (block_k, block_q): lse and delta
    are rows that broadcast along sublanes as they lie, dv and dk take
    plain products, and only dq's takes a transposed left operand."""
    block_q = q_ref.shape[1]
    qi = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]                                 # (1, bq)
    delta = delta_ref[0]
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 1)

    @pl.when(qi == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(kb, dq):
        cols = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        k = k_ref[0, cols, :]
        v = v_ref[0, cols, :]
        st = jax.lax.dot_general(                    # (bk, bq) f32
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * np.float32(scale)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 0)
        mask = q_pos >= k_pos
        if window is not None:
            mask = mask & (q_pos - k_pos < window)
        pt = jnp.where(mask, jnp.exp(st - lse), np.float32(0.0))
        dv_acc[cols, :] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta)).astype(q.dtype)
        dk_acc[cols, :] += jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dq + jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    first_kb, num_kb = _band(qi, block_q, block_k, window)
    dq = jax.lax.fori_loop(
        first_kb, num_kb, body, jnp.zeros((block_q, q_ref.shape[2]),
                                          jnp.float32))
    # s carried one `scale` factor, so dq = scale * (ds @ k_unscaled)
    # and dk = scale * (ds^T @ q_unscaled)
    dq_ref[0] = (dq * np.float32(scale)).astype(dq_ref.dtype)

    @pl.when(qi == pl.num_programs(1) - 1)
    def _():
        dk_ref[0] = (dk_acc[...] * np.float32(scale)).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _named_kernel(name, kernel, **pallas_call_args):
    """``pl.pallas_call`` under ``jax.named_scope(name)`` and with the
    same ``name=``: the kernel is then a row of its own in a device
    trace (the scope ends the custom call's ``op_name``; without it
    the trace names both flash kernels after the flax module they
    sit in)."""
    call = pl.pallas_call(kernel, name=name, **pallas_call_args)

    def named(*operands):
        with jax.named_scope(name):
            return call(*operands)
    return named


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(qf, kf, vf, block_q, block_k, bwd_block_q, bwd_block_k,
           window, interpret):
    out, _ = _flash_fwd_call(qf, kf, vf, block_q, block_k, window,
                             interpret)
    return out


#: the compiler's default scoped VMEM limit: a kernel that asks for no
#: more passes no ``vmem_limit_bytes``
_VMEM_DEFAULT_BYTES = 16 << 20


def _flash_fwd_vmem_bytes(S, D, block_q, block_k, q_dtype, kv_dtype):
    """An upper bound, from the shapes, on what the forward kernel holds
    in VMEM: k and v whole and the q / out blocks (each double-buffered
    by the pipeline; a row fills whole 128-lane tiles), the lse row, the
    body's two score-shaped pairs and its (block_q, D) accumulator, and
    2 MiB to spare.  S 8192 at D 128 or 64 in bfloat16 (the widest call
    of every cell before heads wider than 128) gives 15.0 MiB, under
    the compiler's default."""
    lanes = -(-D // 128) * 128
    held = 2 * 2 * S * lanes * jnp.dtype(kv_dtype).itemsize \
        + 2 * 2 * block_q * lanes * jnp.dtype(q_dtype).itemsize \
        + 2 * 8 * block_q * 4
    body = 4 * block_q * block_k * 4 + 2 * block_q * lanes * 4
    return held + body + (2 << 20)


def _flash_fwd_call(qf, kf, vf, block_q, block_k, window,
                    interpret):
    BH, S, D = qf.shape
    scale = 1.0 / np.sqrt(D)
    # k and v of a (batch, head) wider or longer than the default limit
    # holds (S 8192 at D 192: latent attention's keys): ask for what the
    # shapes need, as the backward kernel does.  Under the default the
    # call is what it was
    vmem = _flash_fwd_vmem_bytes(S, D, block_q, block_k, qf.dtype,
                                 kf.dtype)
    more = {}
    if not interpret and vmem > _VMEM_DEFAULT_BYTES:
        if vmem > _VMEM_USABLE_BYTES:
            raise ValueError(
                f"flash_attention: the forward kernel keeps k and v of "
                f"one (batch, head) in VMEM and asks {vmem} bytes for "
                f"S={S}, D={D}, {qf.dtype.name}; a chip has "
                f"{_VMEM_USABLE_BYTES} to give: shard the sequence "
                f"(parallel/ring_attention.py) or shorten it")
        more["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=vmem)
    out, lse = _named_kernel(
        "flash_fwd",
        functools.partial(_flash_kernel, block_k=block_k, scale=scale,
                          window=window),
        out_shape=(jax.ShapeDtypeStruct((BH, S, D), qf.dtype),
                   jax.ShapeDtypeStruct((BH, 1, S), jnp.float32)),
        grid=(BH, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i))),
        interpret=interpret, **more,
    )(qf, kf, vf)
    return out, lse


def _flash_vjp_fwd(qf, kf, vf, block_q, block_k, bwd_block_q,
                   bwd_block_k, window, interpret):
    out, lse = _flash_fwd_call(qf, kf, vf, block_q, block_k, window,
                               interpret)
    # named so a checkpoint policy can SAVE the kernel's outputs: they
    # are a pallas custom call, not a dot, so no policy of dots keeps
    # them.  Every remat policy of models/transformer.py does, by these
    # names (``_with_remat``), and its backward replay runs no kernel
    from jax.ad_checkpoint import checkpoint_name
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (qf, kf, vf, out, lse)


# VMEM of one TensorCore (v5e, v6e: 128 MiB), less room for what the
# compiler keeps for itself; its default scoped limit is 16 MiB of it
_VMEM_USABLE_BYTES = 112 << 20


def _flash_bwd_vmem_bytes(S, D, block_q, block_k, dtype):
    """An upper bound, from the shapes, on what the backward kernel
    holds in VMEM: k and v whole and the dk / dv output blocks (each
    double-buffered by the pipeline), the two float32 accumulators,
    the q / do / dq blocks and the lse / delta rows (a row fills 8
    sublanes), the loop body's score-shaped and (block, D)
    temporaries, and 2 MiB to spare.  Bisecting the limit off the chip
    (a compile for a described v5e), the compiler takes S 8192, D 128,
    bf16 at 27 MiB where this gives 36.8, and S 32768 at 99 where this
    gives 108.8."""
    item = jnp.dtype(dtype).itemsize
    lanes = -(-D // 128) * 128             # a row fills whole lane tiles
    held = 4 * 2 * S * lanes * item + 2 * S * lanes * 4 \
        + 3 * 2 * block_q * lanes * item + 2 * 2 * 8 * block_q * 4
    body = 8 * block_q * block_k * 4 \
        + 4 * (block_q + block_k) * lanes * 4
    return held + body + (2 << 20)


def _flash_vjp_bwd(block_q, block_k, bwd_block_q, bwd_block_k,
                   window, interpret, res, do):
    # the backward kernel tiles independently of the forward: its
    # per-pair dot chain (5 matmuls + exp) has a different
    # VMEM/pipeline sweet spot than the forward's 2
    block_q, block_k = bwd_block_q, bwd_block_k
    qf, kf, vf, out, lse = res
    BH, S, D = qf.shape
    scale = 1.0 / np.sqrt(D)
    vmem = _flash_bwd_vmem_bytes(S, D, block_q, block_k, qf.dtype)
    if not interpret and vmem > _VMEM_USABLE_BYTES:
        raise ValueError(
            f"flash_attention: the backward kernel keeps k, v, dk and "
            f"dv of one (batch, head) in VMEM and asks {vmem} bytes "
            f"for S={S}, D={D}, {qf.dtype.name}, blocks "
            f"{block_q}x{block_k}; a chip has {_VMEM_USABLE_BYTES} to "
            f"give: shard the sequence (parallel/ring_attention.py) "
            f"or shorten it")
    # delta = rowsum(dO * O) — cheap elementwise, plain XLA; shaped
    # (BH, 1, S) for the TPU block-tiling rule like lse
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]              # (BH, 1, S)
    block = pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0))
    row = pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i))
    whole = pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0))
    # ONE kernel, under the old dkv kernel's scope and name: the
    # benchmark's readers know ``flash_dkv`` (chipbench/scope_join.py)
    dq, dk, dv = _named_kernel(
        "flash_dkv",
        functools.partial(_flash_bwd_kernel, block_k=block_k,
                          scale=scale, window=window),
        out_shape=(jax.ShapeDtypeStruct((BH, S, D), qf.dtype),
                   jax.ShapeDtypeStruct((BH, S, D), kf.dtype),
                   jax.ShapeDtypeStruct((BH, S, D), vf.dtype)),
        grid=(BH, S // block_q),
        in_specs=[block, block, row, row, whole, whole],
        out_specs=(block, whole, whole),
        scratch_shapes=[pltpu.VMEM((S, D), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(qf, do, lse, delta, kf, vf)
    return dq, dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, block_q=512, block_k=512,
                    bwd_block_q=None, bwd_block_k=None,
                    window=None, interpret=None):
    """Causal attention (B, S, H, D) -> (B, S, H, D), flash-style.

    Memory: O(block_q * S) VMEM per program instead of O(S^2) HBM —
    the long-context single-chip workhorse.  The forward (scope and
    name ``flash_fwd``) runs one query block a grid step with k and v
    of the (batch, head) whole in VMEM: an online softmax over the key
    blocks inside the band, two an iteration so that one pair's QK^T
    runs beside the other's softmax, the scores held transposed,
    (block_k, block_q), the running maximum and sum as (1, block_q)
    rows and the accumulator as o^T, all float32; it returns the
    output and each row's logsumexp, (BH, 1, S).  Differentiable: the
    backward pass is ONE pallas kernel (scope and name ``flash_dkv``,
    kept from the dk/dv kernel it grew out of; it yields dq too) that
    recomputes each block pair's probabilities once from the saved
    logsumexp and feeds dq, dk and dv from them, per FlashAttention's
    backward (never materializing the S^2 matrix).  It keeps k, v and
    the float32 dk / dv sums of one (batch, head) in VMEM and asks the
    compiler for the VMEM its shapes need; a sequence too long for a
    chip's VMEM raises a ValueError that names the bytes.
    ``bwd_block_*`` tile the backward kernel independently (its
    5-matmul block body has a different VMEM sweet spot than the
    forward's 2); default: same as the forward blocks.
    ``window`` enables SLIDING-WINDOW attention (mistral-style): each
    query sees only the last ``window`` positions, and both
    kernels skip blocks wholly outside the band — attention cost
    becomes O(S·window) instead of O(S²/2).  Gradient-exact vs
    ``dense_causal_attention(window=...)``.
    """
    if interpret is None:
        interpret = default_interpret()
    B, S, H, D = q.shape
    if window is not None:
        window = int(window)
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if window >= S:
            window = None       # full causal — use the cheaper masks

    # blocks must divide S: clamp, then fall back to the LARGEST
    # divisor of S that still fits under the requested block (NOT the
    # gcd — gcd(512, 1032) is 8, a perf cliff; the largest divisor is
    # 344).  A sequence with no usable divisor would silently become
    # one S-sized block whose (S, S) f32 score tile blows VMEM past
    # ~1k — raise the actionable error instead.
    def _fit_block(requested):
        b = min(requested, S)
        if S % b == 0:
            return b       # explicit/divisible blocks pass unchanged
        b = next(d for d in range(b, 0, -1) if S % d == 0)
        if b < 8:
            if S > 1024:
                raise ValueError(
                    f"flash_attention: seq len {S} has no block "
                    f"divisor in [8, {min(requested, S)}] (S is "
                    f"prime-ish); pad the sequence to a multiple of "
                    f"128 or use dense_causal_attention")
            b = S          # short sequence: one block is cheap
        return b

    block_q = _fit_block(block_q)
    block_k = _fit_block(block_k)
    bwd_block_q = block_q if bwd_block_q is None \
        else _fit_block(bwd_block_q)
    bwd_block_k = block_k if bwd_block_k is None \
        else _fit_block(bwd_block_k)

    # fold batch and heads into the grid's first axis
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    out = _flash(qf, kf, vf, block_q, block_k, bwd_block_q,
                 bwd_block_k, window, interpret)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
