"""Pallas fused 1x1-conv + BatchNorm kernels (the ResNet BN roofline
fix).

ResNet-50 training on one chip is HBM-bound on BatchNorm: BN's stats
pass re-reads every post-conv activation and its normalize pass adds a
read+write (docs/benchmarks.md "Single-chip MFU analysis": deleting BN
is worth 1.26x).  ~5/6 of BN-touched activation bytes sit after 1x1
convs, and a 1x1 conv over NHWC is exactly a matmul
``(B*H*W, Cin) @ (Cin, Cout)`` — so those convs become pallas matmul
kernels that absorb the BN work into tiles already in VMEM:

* **epilogue**: per-channel ``sum`` / ``sum of squares`` of the output
  accumulate in a VMEM scratch while output tiles are written — the
  BN stats pass costs zero extra HBM traffic;
* **prologue**: the PREVIOUS BN's normalize + ReLU is folded into the
  input read as a per-channel affine ``relu(x * a + b)`` — the
  normalize pass of the upstream BN costs zero extra traffic;
* **backward**: one kernel computes ``dx``, ``dw``, ``da``, ``db`` and
  the BN-backward channel reductions in a single pass over
  ``(x, dy, y)`` with both backward matmuls on the MXU.

The reference ships hand-written CUDA where its compiler stopped
helping (``horovod/common/ops/cuda/cuda_kernels.cu:27-292``); this is
the TPU analogue.  Used by ``models/resnet.py`` ``ResNet(fused=True)``
and ``bench.py``.

``interpret=None`` follows the process's platform
(``pallas_kernels.default_interpret``): Mosaic on a TPU, the Pallas
interpreter elsewhere (the CPU tests).  Gradient note: the op returns ``(y, s1, s2)`` and the
custom VJP consumes cotangents for all three, so BN's use of the batch
stats in the downstream fold differentiates exactly (the stats chain
flows through ``ds1``/``ds2``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import default_interpret

__all__ = ["conv1x1_bn", "bn_fold", "supported_m"]


# VMEM block budget for picking the M-block size: double-buffered
# in/out blocks beside the weight tile and (backward) the (K, N) f32
# grad accumulator, inside the raised 64 MB scoped-vmem limit
# (_compiler_params).
_VMEM_BUDGET = 40 * 1024 * 1024


def _pick_bm(m, k, n, backward=False):
    """Largest M-block ≤ 1024 that divides ``m``, is sublane-aligned
    for bf16 (multiple of 16), and fits the VMEM budget.  Returns None
    if no such block exists (caller falls back to the XLA path)."""
    # fixed-resident bytes: weight tile (+ grad accumulator backward)
    fixed = k * n * 2 + (k * n * 4 if backward else 0)
    # per-M-block bytes, double-buffered: fwd reads x and writes y;
    # bwd reads x, dy, y and writes dx
    per_row = (2 * (k + n)) * 2 if not backward \
        else (2 * (2 * k + 2 * n)) * 2
    budget = _VMEM_BUDGET - fixed
    best = None
    for bm in range(16, 1041, 16):
        if m % bm == 0 and bm * per_row <= budget:
            best = bm
    return best


def supported_m(m, k, n):
    """Whether the pallas path can tile an (m, k) x (k, n) problem."""
    return _pick_bm(m, k, n) is not None \
        and _pick_bm(m, k, n, backward=True) is not None


# ---------------------------------------------------------------------------
# forward

def _fwd_kernel(x_ref, a_ref, b_ref, w_ref, y_ref, s1_ref, s2_ref,
                acc1, acc2, *, fold):
    i = pl.program_id(0)
    if fold:
        xh = x_ref[:].astype(jnp.float32) * a_ref[:] + b_ref[:]
        xh = jnp.maximum(xh, 0.0).astype(jnp.bfloat16)
    else:
        xh = x_ref[:]
    y = jnp.dot(xh, w_ref[:], preferred_element_type=jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)

    @pl.when(i == 0)
    def _():
        acc1[:] = jnp.zeros_like(acc1)
        acc2[:] = jnp.zeros_like(acc2)

    acc1[:] += jnp.sum(y, axis=0, keepdims=True)
    acc2[:] += jnp.sum(y * y, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        s1_ref[:] = acc1[:]
        s2_ref[:] = acc2[:]


def _compiler_params(interpret):
    """The stage-4 backward kernels hold a (K, N) f32 grad accumulator
    (up to 8 MB) beside the weight tile — past the compiler's default
    16 MB scoped-vmem limit, well inside the part's physical VMEM
    (the chip compiler takes 64 MB for a v5e:
    tests/test_chip_compile.py)."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=64 * 1024 * 1024)}


def _fwd_call(x, a, b, w, fold, interpret):
    m, k = x.shape
    n = w.shape[1]
    bm = _pick_bm(m, k, n)
    y, s1, s2 = pl.pallas_call(
        functools.partial(_fwd_kernel, fold=fold),
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0)),
                  pl.BlockSpec((1, k), lambda i: (0, 0)),
                  pl.BlockSpec((1, k), lambda i: (0, 0)),
                  pl.BlockSpec((k, n), lambda i: (0, 0))],
        out_specs=(pl.BlockSpec((bm, n), lambda i: (i, 0)),
                   pl.BlockSpec((1, n), lambda i: (0, 0)),
                   pl.BlockSpec((1, n), lambda i: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((m, n), x.dtype),
                   jax.ShapeDtypeStruct((1, n), jnp.float32),
                   jax.ShapeDtypeStruct((1, n), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((1, n), jnp.float32),
                        pltpu.VMEM((1, n), jnp.float32)],
        interpret=interpret,
        **_compiler_params(interpret),
    )(x, a, b, w)
    return y, s1[0], s2[0]


# ---------------------------------------------------------------------------
# backward: one pass over (x, dy, y) producing dx, dw, da, db

def _bwd_kernel(x_ref, a_ref, b_ref, w_ref, dy_ref, y_ref,
                ds1_ref, ds2_ref,
                dx_ref, dw_ref, da_ref, db_ref,
                dw_acc, da_acc, db_acc, *, fold):
    i = pl.program_id(0)
    # total cotangent on the raw output: direct dy plus the stats
    # chain (s1 = sum y, s2 = sum y^2)
    ytot = (dy_ref[:].astype(jnp.float32)
            + ds1_ref[:]
            + 2.0 * y_ref[:].astype(jnp.float32) * ds2_ref[:])
    ytot_bf = ytot.astype(jnp.bfloat16)

    if fold:
        pre = x_ref[:].astype(jnp.float32) * a_ref[:] + b_ref[:]
        mask = (pre > 0.0).astype(jnp.float32)
        xh = jnp.maximum(pre, 0.0).astype(jnp.bfloat16)
    else:
        xh = x_ref[:]

    # dxh = ytot @ w^T  (contract over N)
    dxh = jax.lax.dot_general(
        ytot_bf, w_ref[:], dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == 0)
    def _():
        dw_acc[:] = jnp.zeros_like(dw_acc)
        da_acc[:] = jnp.zeros_like(da_acc)
        db_acc[:] = jnp.zeros_like(db_acc)

    # dw += xh^T @ ytot  (contract over the M block)
    dw_acc[:] += jax.lax.dot_general(
        xh, ytot_bf, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    if fold:
        dxh_m = dxh * mask
        dx_ref[:] = (dxh_m * a_ref[:]).astype(dx_ref.dtype)
        da_acc[:] += jnp.sum(dxh_m * x_ref[:].astype(jnp.float32),
                             axis=0, keepdims=True)
        db_acc[:] += jnp.sum(dxh_m, axis=0, keepdims=True)
    else:
        dx_ref[:] = dxh.astype(dx_ref.dtype)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        dw_ref[:] = dw_acc[:]
        da_ref[:] = da_acc[:]
        db_ref[:] = db_acc[:]


def _bwd_call(x, a, b, w, y, dy, ds1, ds2, fold, interpret):
    m, k = x.shape
    n = w.shape[1]
    bm = _pick_bm(m, k, n, backward=True)
    dx, dw, da, db = pl.pallas_call(
        functools.partial(_bwd_kernel, fold=fold),
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0)),
                  pl.BlockSpec((1, k), lambda i: (0, 0)),
                  pl.BlockSpec((1, k), lambda i: (0, 0)),
                  pl.BlockSpec((k, n), lambda i: (0, 0)),
                  pl.BlockSpec((bm, n), lambda i: (i, 0)),
                  pl.BlockSpec((bm, n), lambda i: (i, 0)),
                  pl.BlockSpec((1, n), lambda i: (0, 0)),
                  pl.BlockSpec((1, n), lambda i: (0, 0))],
        out_specs=(pl.BlockSpec((bm, k), lambda i: (i, 0)),
                   pl.BlockSpec((k, n), lambda i: (0, 0)),
                   pl.BlockSpec((1, k), lambda i: (0, 0)),
                   pl.BlockSpec((1, k), lambda i: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((m, k), x.dtype),
                   jax.ShapeDtypeStruct((k, n), jnp.float32),
                   jax.ShapeDtypeStruct((1, k), jnp.float32),
                   jax.ShapeDtypeStruct((1, k), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((k, n), jnp.float32),
                        pltpu.VMEM((1, k), jnp.float32),
                        pltpu.VMEM((1, k), jnp.float32)],
        interpret=interpret,
        **_compiler_params(interpret),
    )(x, a, b, w, dy, y, ds1.reshape(1, n), ds2.reshape(1, n))
    return dx, dw, da[0], db[0]


# ---------------------------------------------------------------------------
# public op with custom VJP

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _conv1x1_bn(x, a, b, w, fold, interpret):
    return _fwd_call(x, a, b, w, fold, interpret)


def _vjp_fwd(x, a, b, w, fold, interpret):
    y, s1, s2 = _fwd_call(x, a, b, w, fold, interpret)
    return (y, s1, s2), (x, a, b, w, y)


def _bwd_xla(x, a, b, w, y, dy, ds1, ds2, fold):
    """XLA backward with the same math as _bwd_kernel (A/B lever and
    oracle; env HOROVOD_CONV_BN_BWD=xla selects it)."""
    ytot = (dy.astype(jnp.float32) + ds1[None, :]
            + 2.0 * y.astype(jnp.float32) * ds2[None, :])
    ytot_bf = ytot.astype(jnp.bfloat16)
    if fold:
        pre = x.astype(jnp.float32) * a + b
        mask = (pre > 0.0).astype(jnp.float32)
        xh = jnp.maximum(pre, 0.0).astype(jnp.bfloat16)
    else:
        xh = x
    dxh = jax.lax.dot_general(
        ytot_bf, w, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dw = jax.lax.dot_general(
        xh, ytot_bf, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if fold:
        dxh_m = dxh * mask
        dx = (dxh_m * a).astype(x.dtype)
        da = jnp.sum(dxh_m * x.astype(jnp.float32), axis=0,
                     keepdims=True)
        db = jnp.sum(dxh_m, axis=0, keepdims=True)
    else:
        dx = dxh.astype(x.dtype)
        da = db = None
    return dx, dw, da, db


def _bwd_mode():
    from ..common import env

    return env.get_str(env.HOROVOD_CONV_BN_BWD, "pallas")


def _vjp_bwd(fold, interpret, res, cots):
    x, a, b, w, y = res
    dy, ds1, ds2 = cots
    if _bwd_mode() == "xla":
        dx, dw, da, db = _bwd_xla(x, a, b, w, y, dy, ds1, ds2, fold)
    else:
        dx, dw, da, db = _bwd_call(x, a, b, w, y, dy, ds1, ds2,
                                   fold, interpret)
    if not fold or da is None:
        da = jnp.zeros_like(a)
        db = jnp.zeros_like(b)
    else:
        da = da.reshape(a.shape)
        db = db.reshape(b.shape)
    return dx, da, db, dw


_conv1x1_bn.defvjp(_vjp_fwd, _vjp_bwd)


def _reference(x, a, b, w, fold):
    """XLA fallback with identical semantics (also the test oracle)."""
    if fold:
        xh = jnp.maximum(x.astype(jnp.float32) * a + b, 0.0)
        xh = xh.astype(jnp.bfloat16)
    else:
        xh = x
    y = jnp.dot(xh, w, preferred_element_type=jnp.float32)
    s1 = jnp.sum(y, axis=0)
    s2 = jnp.sum(y * y, axis=0)
    return y.astype(x.dtype), s1, s2


def conv1x1_bn(x, w, fold=None, *, interpret=None, use_pallas=None):
    """Fused ``y = relu(x*a + b) @ w`` (or plain ``x @ w``) returning
    ``(y, colsum(y), colsum(y^2))`` in one HBM pass over ``x``.

    Args:
      x: ``(M, K)`` activations (bf16 on TPU).
      w: ``(K, N)`` weights.
      fold: optional ``(a, b)`` per-channel f32 affine of shape
        ``(1, K)`` — the upstream BN's normalize (+ReLU) folded into
        the input read.  ``None`` = consume ``x`` as-is.
    Returns:
      ``(y, s1, s2)`` with ``y`` in ``x.dtype`` and per-channel f32
      sums for the downstream BN.
    """
    m, k = x.shape
    n = w.shape[1]
    do_fold = fold is not None
    a, b = fold if do_fold else (jnp.ones((1, k), jnp.float32),
                                 jnp.zeros((1, k), jnp.float32))
    a = a.reshape(1, k).astype(jnp.float32)
    b = b.reshape(1, k).astype(jnp.float32)
    if use_pallas is None:
        use_pallas = supported_m(m, k, n)
    if not use_pallas:
        return _reference(x, a, b, w, do_fold)
    if interpret is None:
        interpret = default_interpret()
    return _conv1x1_bn(x, a, b, w, do_fold, interpret)


def bn_fold(s1, s2, count, scale, bias, epsilon=1e-5):
    """Batch-stat fold: per-channel ``(a, b)`` such that
    ``y*a + b == scale * (y - mean)/sqrt(var+eps) + bias``."""
    mean = s1 / count
    var = s2 / count - mean * mean
    inv = scale * jax.lax.rsqrt(var + epsilon)
    return inv, bias - mean * inv
