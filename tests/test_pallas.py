"""Pallas kernel tests (interpret mode on CPU; same code compiles to
Mosaic on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import dense_causal_attention
from horovod_tpu.ops.pallas_kernels import flash_attention, fused_scale_cast


def test_fused_scale_cast():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,), jnp.float32)
    out = fused_scale_cast(x, 0.5, jnp.bfloat16, block=256,
                           interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(x) * 0.5, rtol=1e-2)


def test_fused_scale_cast_nonmultiple_block():
    x = jnp.ones((7, 13), jnp.float32)
    out = fused_scale_cast(x, 3.0, interpret=True, block=32)
    assert out.shape == (7, 13)
    np.testing.assert_allclose(np.asarray(out), 3.0)


def test_flash_attention_matches_dense():
    B, S, H, D = 2, 64, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in keys)
    out = flash_attention(q, k, v, block_q=16, block_k=16,
                          interpret=True)
    ref = dense_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_nondividing_default_blocks():
    """Sequence lengths that divided the old 128 default but not the
    512 default (e.g. S=24, S=12) must still work — the block falls
    back to a common divisor instead of raising."""
    for S in (24, 12):
        B, H, D = 1, 1, 8
        keys = jax.random.split(jax.random.PRNGKey(2), 3)
        q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
                   for kk in keys)
        out = flash_attention(q, k, v, interpret=True)
        ref = dense_causal_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_flash_attention_uneven_blocks():
    B, S, H, D = 1, 32, 1, 8
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in keys)
    out = flash_attention(q, k, v, block_q=8, block_k=16,
                          interpret=True)
    ref = dense_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_sliding_window():
    """window=W (mistral-style sliding window) must match the dense
    windowed reference in values AND gradients, across window sizes
    that hit every block-boundary case (W < block, W % block != 0,
    W = S, W > S degenerating to full causal)."""
    from functools import partial

    from horovod_tpu.models.transformer import dense_causal_attention

    B, S, H, D = 2, 64, 2, 8
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D)) for kk in keys)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    # unequal block pairs included: the block-skip bounds (first_kb
    # floor division, dkv num_qb clamp) depend on the block ratio
    for bq, bk in ((16, 16), (32, 8), (8, 32)):
        for W in (1, 5, 16, 17, 63, 64, 200):
            dense_w = W if W < S else None
            flash = partial(flash_attention, block_q=bq, block_k=bk,
                            window=W, interpret=True)
            out = flash(q, k, v)
            ref = dense_causal_attention(q, k, v, window=dense_w)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), rtol=2e-5,
                atol=2e-5, err_msg=f"bq={bq} bk={bk} W={W}")
            gf = jax.grad(partial(loss, flash),
                          argnums=(0, 1, 2))(q, k, v)
            gd = jax.grad(partial(loss, partial(
                dense_causal_attention, window=dense_w)),
                argnums=(0, 1, 2))(q, k, v)
            for a, b in zip(gf, gd):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=5e-5,
                    atol=5e-5, err_msg=f"bq={bq} bk={bk} W={W}")

    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, window=0, interpret=True)


def test_flash_attention_independent_bwd_blocks():
    """bwd_block_q/bwd_block_k tile the backward kernels independently
    of the forward; gradients must be identical to the shared-block
    path."""
    from functools import partial

    B, S, H, D = 1, 64, 2, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))

    def loss(fn, q):
        return jnp.sum(fn(q, q, q) ** 2)

    g_ref = jax.grad(partial(loss, partial(
        flash_attention, block_q=16, block_k=16, interpret=True)))(q)
    g_bwd = jax.grad(partial(loss, partial(
        flash_attention, block_q=16, block_k=16, bwd_block_q=32,
        bwd_block_k=8, interpret=True)))(q)
    np.testing.assert_allclose(np.asarray(g_ref), np.asarray(g_bwd),
                               rtol=1e-5, atol=1e-5)


# (dtype, B, H, bwd_block_q, bwd_block_k, window): the ONE backward
# kernel against the dense reference's gradients.  S 64 with blocks of
# 16 gives every key block up to four query blocks, so the float32
# dk / dv accumulators are summed into across grid steps; a window of
# 5 / 17 / 63 ends inside a block; B*H > 1 makes a second (batch, head)
# start from zeroed accumulators; unequal blocks both ways move the
# band's block bounds.
_BWD_CASES = [
    (jnp.float32, 1, 1, 16, 16, None),
    (jnp.bfloat16, 1, 1, 16, 16, None),
    (jnp.float32, 1, 1, 16, 16, 5),
    (jnp.float32, 1, 1, 16, 16, 17),
    (jnp.float32, 1, 1, 16, 16, 63),
    (jnp.bfloat16, 2, 2, 16, 16, 17),
    (jnp.float32, 1, 1, 32, 8, None),
    (jnp.float32, 1, 1, 8, 32, None),
    (jnp.float32, 1, 2, 32, 8, 17),
    (jnp.float32, 2, 1, 8, 32, 17),
    (jnp.float32, 2, 3, 16, 16, None),
    (jnp.bfloat16, 2, 2, 8, 32, 63),
]


@pytest.mark.parametrize(
    "dtype,B,H,bq,bk,window", _BWD_CASES,
    ids=[f"{np.dtype(c[0]).name}-B{c[1]}H{c[2]}-{c[3]}x{c[4]}-W{c[5]}"
         for c in _BWD_CASES])
def test_flash_backward_one_kernel_matches_dense(dtype, B, H, bq, bk,
                                                 window):
    from functools import partial

    S, D = 64, 16
    keys = jax.random.split(jax.random.PRNGKey(B * 7 + H), 4)
    q, k, v, w = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
                  for kk in keys)

    def loss(fn, q, k, v):
        # a weight per output element, so no head's gradient is a
        # multiple of another's
        return jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    flash = partial(flash_attention, block_q=16, block_k=16,
                    bwd_block_q=bq, bwd_block_k=bk, window=window,
                    interpret=True)
    dense = partial(dense_causal_attention, window=window)
    cast = [x.astype(dtype) for x in (q, k, v)]
    got = jax.grad(partial(loss, flash), argnums=(0, 1, 2))(*cast)
    # the reference in float32 from the SAME (rounded) inputs
    want = jax.grad(partial(loss, dense), argnums=(0, 1, 2))(
        *[x.astype(jnp.float32) for x in cast])
    tol = 5e-5 if dtype == jnp.float32 else 2.5e-2
    for name, a, b in zip("qkv", got, want):
        assert a.dtype == dtype and a.shape == b.shape
        a = np.asarray(a.astype(jnp.float32))
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, np.asarray(b), rtol=tol, atol=tol,
                                   err_msg=f"d{name}")


# (dtype, BH, S, D, block_q, block_k, window): the forward kernel alone
# (`_flash_fwd_call`) against the dense reference's output and a float32
# logsumexp.  The band's key blocks go through the loop two an
# iteration and what is left over one at a time, so bands of one, two
# and three blocks and longer ones are here; a window of 5 / 17 / 63 ends
# inside a block, and with several query blocks to a key block
# (block_q 8, block_k 32) the later query blocks' first key block is
# WHOLLY masked for some rows: there `p` must be 0 and `l` unchanged
# with no second mask after the `exp`.
_FWD_CASES = [
    (jnp.float32, 1, 64, 16, 16, 16, None),
    (jnp.bfloat16, 1, 64, 16, 16, 16, None),
    (jnp.float32, 1, 64, 16, 64, 64, None),     # a band one block long
    (jnp.float32, 1, 32, 16, 16, 16, None),     # bands of one and two
    (jnp.float32, 1, 48, 16, 16, 16, None),     # ... and three
    (jnp.float32, 1, 64, 16, 16, 16, 5),
    (jnp.float32, 1, 64, 16, 16, 16, 17),
    (jnp.float32, 1, 64, 16, 16, 16, 63),
    (jnp.float32, 1, 64, 8, 8, 32, 5),          # rows wholly masked in
    (jnp.float32, 1, 64, 8, 8, 32, 17),         # the band's first block
    (jnp.float32, 1, 64, 8, 8, 32, 63),
    (jnp.bfloat16, 3, 64, 8, 8, 32, 17),
    (jnp.float32, 1, 64, 8, 32, 8, None),       # block_q > block_k
    (jnp.float32, 2, 64, 8, 32, 8, 17),
    (jnp.bfloat16, 4, 64, 16, 32, 8, 63),
    (jnp.float32, 1, 64, 8, 8, 32, None),       # block_q < block_k
    (jnp.float32, 6, 96, 16, 16, 16, 40),       # B*H > 1, odd band
    (jnp.float32, 2, 256, 128, 128, 128, None),     # the cells' D
    (jnp.bfloat16, 2, 256, 128, 128, 128, 130),
]


@pytest.mark.parametrize(
    "dtype,BH,S,D,bq,bk,window", _FWD_CASES,
    ids=[f"{np.dtype(c[0]).name}-BH{c[1]}-S{c[2]}D{c[3]}-{c[4]}x{c[5]}"
         f"-W{c[6]}" for c in _FWD_CASES])
def test_flash_forward_matches_dense(dtype, BH, S, D, bq, bk, window):
    from horovod_tpu.ops.pallas_kernels import _flash_fwd_call

    keys = jax.random.split(jax.random.PRNGKey(S + D + bq), 3)
    q, k, v = (jax.random.normal(kk, (BH, S, D), jnp.float32)
               .astype(dtype) for kk in keys)
    out, lse = _flash_fwd_call(q, k, v, bq, bk, window, True)
    assert out.shape == (BH, S, D) and out.dtype == dtype
    assert lse.shape == (BH, 1, S) and lse.dtype == jnp.float32
    # the references in float32 from the SAME (rounded) inputs
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    want = dense_causal_attention(q32[:, :, None], k32[:, :, None],
                                  v32[:, :, None], window=window)[:, :, 0]
    scores = jnp.einsum("bqd,bkd->bqk", q32, k32) / np.sqrt(D)
    pos = jnp.arange(S)
    mask = pos[:, None] >= pos[None, :]
    if window is not None:
        mask = mask & (pos[:, None] - pos[None, :] < window)
    want_lse = jax.scipy.special.logsumexp(
        jnp.where(mask, scores, -jnp.inf), axis=-1)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    got = np.asarray(out.astype(jnp.float32))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(lse[:, 0]), np.asarray(want_lse),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# block-scaled int8 wire codec (quantized collectives)

def test_quantize_blockwise_matches_numpy_codec():
    """The Pallas encoder and the numpy wire codec (ops/quantize.py)
    must agree bit-for-bit: error-feedback residuals re-run the codec
    host-side and rely on encode(x) being one pure function."""
    from horovod_tpu.ops import quantize as qz
    from horovod_tpu.ops.pallas_kernels import (
        dequantize_blockwise, quantize_blockwise)

    x = np.random.default_rng(0).standard_normal(70_000) \
        .astype(np.float32)
    q, s = quantize_blockwise(jnp.asarray(x), interpret=True)
    qn, sn, n = qz.np_quantize_blockwise(x)
    assert np.array_equal(np.asarray(q)[:qn.size], qn)
    np.testing.assert_array_equal(np.asarray(s)[:sn.size],
                                  sn.astype(np.float32))
    out = dequantize_blockwise(q, s, n, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), qz.np_dequantize_blockwise(qn, sn, n))


def test_quantize_blockwise_xla_matches_numpy_codec():
    """Third implementation of the same contract: the pure-XLA codec
    (used inside the executor's quantized collective programs) must
    match the numpy wire codec bit-for-bit too."""
    from horovod_tpu.ops import quantize as qz

    x = np.random.default_rng(3).standard_normal(70_000) \
        .astype(np.float32)
    q, s = qz.quantize_blockwise_xla(jnp.asarray(x))
    qn, sn, n = qz.np_quantize_blockwise(x)
    assert np.array_equal(np.asarray(q)[:qn.size], qn)
    np.testing.assert_array_equal(np.asarray(s)[:sn.size],
                                  sn.astype(np.float32))
    out = qz.dequantize_blockwise_xla(q, s, n)
    np.testing.assert_array_equal(
        np.asarray(out), qz.np_dequantize_blockwise(qn, sn, n))


def test_quantize_blockwise_error_bound():
    """Per-element error is bounded by half the block scale
    (absmax / 254) — the property the int8 wire's accuracy story
    rests on."""
    from horovod_tpu.ops.pallas_kernels import (
        dequantize_blockwise, quantize_blockwise)

    x = (np.random.default_rng(1).standard_normal(4096) * 7) \
        .astype(np.float32)
    q, s = quantize_blockwise(jnp.asarray(x), interpret=True)
    out = np.asarray(dequantize_blockwise(q, s, x.size,
                                          interpret=True))
    blocks = x.reshape(-1, 256)
    bound = (np.abs(blocks).max(axis=1) / 254 + 1e-7)[:, None]
    assert np.all(np.abs(out.reshape(-1, 256) - blocks) <= bound * 1.01)


def test_fake_quantize_blockwise_vjp_is_straight_through():
    """Custom VJP contract: gradients are exact w.r.t. the DEQUANTIZED
    value — d/dx sum(c * fq(x)) == c, not the a.e.-zero derivative of
    round()."""
    from horovod_tpu.ops import quantize as qz
    from horovod_tpu.ops.pallas_kernels import fake_quantize_blockwise

    x = jnp.asarray(np.random.default_rng(2)
                    .standard_normal((3, 700)).astype(np.float32))
    fq = fake_quantize_blockwise(x)
    np.testing.assert_array_equal(
        np.asarray(fq), qz.np_fake_quantize_blockwise(np.asarray(x)))
    g = jax.grad(lambda v: jnp.sum(fake_quantize_blockwise(v) * 3.0))(x)
    np.testing.assert_array_equal(np.asarray(g),
                                  np.full(x.shape, 3.0, np.float32))


def test_quantize_blockwise_int4_matches_numpy_codec():
    """All three int4 implementations (numpy / pure-XLA / Pallas) must
    agree bit-for-bit — packed nibbles AND bf16 scales — the same
    purity contract the int8 codec carries (error feedback re-runs
    the encoder host-side)."""
    import jax.numpy as jnp

    from horovod_tpu.ops import quantize as qz
    from horovod_tpu.ops.pallas_kernels import (
        dequantize_blockwise_int4, quantize_blockwise_int4)

    x = np.random.default_rng(5).standard_normal(70_000) \
        .astype(np.float32)
    qn, sn, n = qz.np_quantize_blockwise_int4(x)
    # pallas
    q, s = quantize_blockwise_int4(jnp.asarray(x), interpret=True)
    assert np.array_equal(np.asarray(q)[:qn.size], qn)
    np.testing.assert_array_equal(np.asarray(s)[:sn.size],
                                  sn.astype(np.float32))
    out = dequantize_blockwise_int4(q, s, n, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), qz.np_dequantize_blockwise_int4(qn, sn, n))
    # pure XLA
    qx, sx = qz.quantize_blockwise_int4_xla(jnp.asarray(x))
    assert np.array_equal(np.asarray(qx), qn)
    np.testing.assert_array_equal(np.asarray(sx),
                                  sn.astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(qz.dequantize_blockwise_int4_xla(qx, sx, n)),
        qz.np_dequantize_blockwise_int4(qn, sn, n))


def test_int4_nibble_pack_roundtrip_property():
    """Property test over the full code range: every int4 code in
    [-7, 7], at every parity position, survives pack -> unpack
    exactly (the biased-nibble layout is lossless by construction)."""
    from horovod_tpu.ops import quantize as qz

    rng = np.random.default_rng(7)
    for _ in range(20):
        q = rng.integers(-7, 8, size=512).astype(np.int8)
        np.testing.assert_array_equal(
            qz.np_unpack_nibbles(qz.np_pack_nibbles(q)), q)
    # exhaustive pair coverage: all 15 x 15 nibble combinations
    lo, hi = np.meshgrid(np.arange(-7, 8), np.arange(-7, 8))
    q = np.stack([lo.ravel(), hi.ravel()], axis=1).reshape(-1) \
        .astype(np.int8)
    np.testing.assert_array_equal(
        qz.np_unpack_nibbles(qz.np_pack_nibbles(q)), q)


def test_quantize_blockwise_int4_error_bound():
    """Per-element error is bounded by half the block scale
    (absmax / 14) — the bound the int4 wire's accuracy story (and
    the WIRE_ATOL the op matrix uses) rests on."""
    from horovod_tpu.ops import quantize as qz

    x = (np.random.default_rng(11).standard_normal(8192) * 5) \
        .astype(np.float32)
    out = qz.np_fake_quantize_blockwise_int4(x)
    blocks = x.reshape(-1, 256)
    bound = (np.abs(blocks).max(axis=1) / 14 + 1e-7)[:, None]
    assert np.all(np.abs(out.reshape(-1, 256) - blocks)
                  <= bound * 1.01)


def test_fake_quantize_blockwise_int4_vjp_is_straight_through():
    from horovod_tpu.ops import quantize as qz
    from horovod_tpu.ops.pallas_kernels import \
        fake_quantize_blockwise_int4

    x = jnp.asarray(np.random.default_rng(13)
                    .standard_normal((2, 600)).astype(np.float32))
    fq = fake_quantize_blockwise_int4(x)
    np.testing.assert_array_equal(
        np.asarray(fq),
        qz.np_fake_quantize_blockwise_int4(np.asarray(x)))
    g = jax.grad(
        lambda v: jnp.sum(fake_quantize_blockwise_int4(v) * 2.0))(x)
    np.testing.assert_array_equal(np.asarray(g),
                                  np.full(x.shape, 2.0, np.float32))


def test_quantized_psum_acc_bounds():
    """The documented exact-rank bounds: the accumulator is the
    narrowest integer whose psum of maxed-out codes stays exact —
    int4 rides an int8 operand (half int8's transport) to 18 ranks."""
    from horovod_tpu.ops import quantize as qz

    assert qz.quantized_acc_dtype_np(8, 258) == np.dtype(np.int16)
    assert qz.quantized_acc_dtype_np(8, 259) == np.dtype(np.int32)
    assert qz.quantized_acc_dtype_np(4, 18) == np.dtype(np.int8)
    assert qz.quantized_acc_dtype_np(4, 19) == np.dtype(np.int16)
    assert qz.quantized_acc_dtype_np(4, 4681) == np.dtype(np.int16)
    assert qz.quantized_acc_dtype_np(4, 4682) == np.dtype(np.int32)
    # wire accounting follows the operand width
    n = 1 << 20
    assert qz.quantized_psum_wire_nbytes(n, 2, bits=4) < \
        qz.quantized_psum_wire_nbytes(n, 2, bits=8)


def test_quantize_blockwise_zero_and_tiny_blocks():
    """All-zero blocks encode with scale 0 and decode to exact zeros;
    sub-block inputs pad with zeros that round-trip losslessly."""
    from horovod_tpu.ops.pallas_kernels import (
        dequantize_blockwise, quantize_blockwise)

    x = np.zeros(300, np.float32)
    x[:7] = [1e-30, -1e-30, 0.5, -0.5, 2.0, -2.0, 1e20]
    q, s = quantize_blockwise(jnp.asarray(x), interpret=True)
    out = np.asarray(dequantize_blockwise(q, s, x.size,
                                          interpret=True))
    assert out.shape == x.shape
    assert np.all(np.isfinite(out[:256]) | (x[:256] > 1e19))
    np.testing.assert_array_equal(out[256:], np.zeros(44, np.float32))
