"""The two precisions a reference is computed in.

``float32``: every matrix product at ``highest`` precision (on a TPU a
float32 product otherwise runs in fewer bfloat16 passes).

``fp8`` and ``int8``: the controls.  The configurations here state
bfloat16 products over float32 parameters; the nearest precision below
is eight bits, the step that would tempt a later PR (the v5e multiplies
int8 at twice its bfloat16 rate).  Every product takes its two operands
rounded under a per-tensor scale, and in the backward pass the incoming
gradient too: ``fp8`` to e4m3 and e5m2, the usual fp8 training recipe;
``int8`` to 255 levels each.  Accumulation stays float32.
``limits/<cell>.json`` names the cell's control.
"""

import functools

import jax
import jax.numpy as jnp

MODES = ("float32", "fp8", "int8")
_HIGHEST = jax.lax.Precision.HIGHEST


def _round_to(x, dtype):
    """``x`` as ``dtype`` holds it under a per-tensor scale, in float32;
    ``dtype`` is an 8-bit float type or ``"int8"``."""
    amax = jnp.max(jnp.abs(x))
    if dtype == "int8":
        scale = jnp.where(amax > 0, 127.0 / amax, 1.0)
        return jnp.clip(jnp.round(x * scale), -127, 127) / scale
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


_FORMATS = {"fp8": (jnp.float8_e4m3fn, jnp.float8_e5m2),
            "int8": ("int8", "int8")}


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _rounded_product(fn, mode, a, b):
    return _rounded_fwd(fn, mode, a, b)[0]


def _rounded_fwd(fn, mode, a, b):
    qa = _round_to(a, _FORMATS[mode][0])
    qb = _round_to(b, _FORMATS[mode][0])
    return fn(qa, qb), (qa, qb)


def _rounded_bwd(fn, mode, res, g):
    _, vjp = jax.vjp(fn, *res)
    return vjp(_round_to(g, _FORMATS[mode][1]))


_rounded_product.defvjp(_rounded_fwd, _rounded_bwd)


def products(mode):
    """(einsum, conv) for ``mode``: ``einsum(spec, a, b)`` and
    ``conv(x, w, strides, padding)`` (NHWC x HWIO) in float32."""
    if mode not in MODES:
        raise ValueError(f"precision mode {mode!r} is not one of {MODES}")

    def conv_fn(strides, padding):
        return functools.partial(
            jax.lax.conv_general_dilated, window_strides=strides,
            padding=padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=_HIGHEST)

    if mode == "float32":
        def einsum(spec, a, b):
            return jnp.einsum(spec, a, b, precision=_HIGHEST)

        def conv(x, w, strides, padding):
            return conv_fn(strides, padding)(x, w)
    else:
        def einsum(spec, a, b):
            return _rounded_product(
                functools.partial(jnp.einsum, spec, precision=_HIGHEST),
                mode, a, b)

        def conv(x, w, strides, padding):
            return _rounded_product(conv_fn(strides, padding), mode, x, w)
    return einsum, conv
