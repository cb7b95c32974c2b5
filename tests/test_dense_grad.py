"""``models/dense.dense_product``: the product of every dense projection
of a layer, whose written backward hands the weight's gradient over in
the weight's own layout (PERF.md section 6, PR 49)."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.ad_checkpoint import checkpoint_policies
from jax._src.ad_checkpoint import saved_residuals

from horovod_tpu.models import dense
from horovod_tpu.models.dense import dense_product

B, S, M, F, H, D = 2, 8, 16, 24, 4, 8

#: the call sites' shapes: name -> (the flax module but for its product,
#: the input's shape, the kernel's shape)
SITES = {
    # SwiGLU's three (transformer.py), the mixers' two (mamba.py)
    "kernel_2d": (functools.partial(nn.Dense, F), (B, S, M), (M, F)),
    # Attention._heads: wq, wk, wv, wg
    "kernel_in_h_d": (functools.partial(nn.DenseGeneral, (H, D), axis=-1),
                      (B, S, M), (M, H, D)),
    # Attention's wo, contracted over two axes
    "kernel_h_d_out": (functools.partial(nn.DenseGeneral, M, axis=(-2, -1)),
                       (B, S, H, D), (H, D, M)),
}
DTYPES = [jnp.float32, jnp.bfloat16]


def _site(name, dtype, product=None):
    """(module, parameters, x) of one call site in one compute dtype;
    the parameters float32, as every layer's."""
    make, x_shape, kernel_shape = SITES[name]
    module = make(use_bias=False, dtype=dtype, param_dtype=jnp.float32,
                  dot_general=product)
    x = jax.random.normal(jax.random.key(0), x_shape, jnp.float32)
    params = module.init(jax.random.key(1), x)
    assert params["params"]["kernel"].shape == kernel_shape
    return module, params, x.astype(dtype)


def _loss(module):
    def loss(params, x):
        # a cotangent that differs from element to element
        return jnp.sum(jnp.sin(module.apply(params, x).astype(jnp.float32)))
    return loss


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("site", sorted(SITES))
def test_written_backward_equals_autodiffs(site, dtype):
    """dx and dW are those of ``jax.grad`` through flax's default
    product: the same sums in another order, so equal to within one
    unit of bfloat16 (a float32 sum rounded once) and a few of float32,
    at the leaf's largest element; dW float32 in the kernel's shape, as
    the cast's own transpose makes it."""
    plain, params, x = _site(site, dtype)
    written, _, _ = _site(site, dtype, dense_product)
    np.testing.assert_array_equal(written.apply(params, x),
                                  plain.apply(params, x))
    want = jax.grad(_loss(plain), (0, 1))(params, x)
    got = jax.grad(_loss(written), (0, 1))(params, x)
    unit = float(jnp.finfo(dtype).eps) * (4 if dtype == jnp.float32 else 1)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        assert np.abs(g - w).max() <= unit * max(np.abs(w).max(), 1.0)
    assert got[0]["params"]["kernel"].dtype == jnp.float32


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("site", sorted(SITES))
def test_weight_gradient_is_not_transposed(site, dtype):
    """The gradient's jaxpr holds no ``transpose`` whose result has the
    kernel's shape (the default product's does: that is what the TPU
    compiler folds into the layout the optimizer inherits), and under
    ``vmap``, as the one-device step runs its loss, none either."""
    def kernel_transposes(module, params, x):
        shape = params["params"]["kernel"].shape
        grad = jax.grad(_loss(module))
        found = []
        for fn, lead in ((grad, ()), (jax.vmap(grad), (1,))):
            args = jax.tree.map(lambda a: a.reshape(lead + a.shape),
                                (params, x))
            found.append([
                eqn for eqn in _equations(jax.make_jaxpr(fn)(*args).jaxpr)
                if eqn.primitive.name == "transpose"
                and eqn.outvars[0].aval.shape == lead + shape])
        return found

    assert all(kernel_transposes(*_site(site, dtype)))
    assert not any(kernel_transposes(*_site(site, dtype, dense_product)))


def _nested_forward_product():
    """The planted fault: a product like ``dense_product`` but for its
    forward rule, which calls the ``custom_vjp`` function again."""
    @functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
    def nested(x, w, dimension_numbers, precision=None):
        return lax.dot_general(x, w, dimension_numbers, precision=precision)

    nested.defvjp(
        lambda x, w, dn, precision: (nested(x, w, dn, precision), (x, w)),
        dense._backward)
    return nested


def _saved(module, params, x):
    """What a ``dots`` remat of the projection keeps for its backward:
    [(shape, dtype)] of the residuals that are not arguments."""
    fn = jax.checkpoint(
        _loss(module),
        policy=checkpoint_policies.dots_with_no_batch_dims_saveable)
    return sorted((aval.shape, str(aval.dtype))
                  for aval, why in saved_residuals(fn, params, x)
                  if "argument" not in why)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("site", sorted(SITES))
def test_dots_policy_still_sees_the_product(site, dtype):
    """Under ``jax.checkpoint`` with ``dots_with_no_batch_dims_saveable``
    the saved residuals are the default product's, the product's output
    among them; a forward rule that calls the ``custom_vjp`` function
    again hides the product from the policy, which this catches."""
    plain, params, x = _site(site, dtype)
    want = _saved(plain, params, x)
    out = jax.eval_shape(plain.apply, params, x)
    assert (out.shape, out.dtype.name) in want
    assert _saved(*_site(site, dtype, dense_product)) == want
    assert _saved(*_site(site, dtype, _nested_forward_product())) != want


@pytest.mark.parametrize("x_shape, w_shape, dimension_numbers", [
    ((3, 4, 5), (3, 5, 6), (((2,), (1,)), ((0,), (0,)))),   # a batch axis
    ((2, 5, 4), (5, 6), (((1,), (0,)), ((), ()))),   # contracted mid-x
    ((2, 4, 5), (6, 5), (((2,), (1,)), ((), ()))),   # w's trailing axis
    ((2, 4, 5), (5, 4, 6), (((1, 2), (1, 0)), ((), ()))),   # out of order
])
def test_other_products_are_refused(x_shape, w_shape, dimension_numbers):
    """Only what flax's layers ask for has the written backward; any
    other contraction is refused while tracing, forward-only use too."""
    x, w = jnp.ones(x_shape), jnp.ones(w_shape)
    assert lax.dot_general(x, w, dimension_numbers).ndim
    with pytest.raises(ValueError, match="trailing axes"):
        dense_product(x, w, dimension_numbers)
    with pytest.raises(ValueError, match="trailing axes"):
        jax.grad(lambda x: dense_product(x, w, dimension_numbers).sum())(x)


@pytest.mark.parametrize("limit, written", [(64, True), (63, False)])
def test_a_layer_takes_it_up_to_the_rows_it_pays_at(limit, written,
                                                    monkeypatch):
    """A model with a mamba layer, an attention layer and a gated
    sliding one, through the fused loss, 2 x 32 rows a step.  Within
    ``WRITTEN_BACKWARD_ROWS`` its gradient transposes no projection's
    kernel; one row over, every projection keeps jax's rule, which
    transposes each."""
    from horovod_tpu.models import transformer
    from horovod_tpu.models.transformer import (TransformerConfig,
                                                TransformerLM,
                                                make_fused_lm_loss)

    monkeypatch.setattr(transformer, "WRITTEN_BACKWARD_ROWS", limit)
    model = TransformerLM(TransformerConfig(
        vocab_size=64, d_model=32, n_layers=3, n_heads=2, n_kv_heads=1,
        head_dim=16, d_ff=48, max_seq_len=32, attention_gate=True,
        layer_types=("mamba", "full_attention", "sliding_attention"),
        sliding_window=8, mamba_n_heads=4, mamba_d_head=8, mamba_d_state=8,
        mamba_chunk_size=8))
    tokens = jnp.zeros((2, 32), jnp.int32)
    params = model.init(jax.random.key(0), tokens)["params"]
    projections = {
        jax.tree_util.keystr(path): leaf.shape[1:]
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
        if "kernel" in jax.tree_util.keystr(path)
        and "conv" not in jax.tree_util.keystr(path)}
    # in_proj, out_proj; wq, wk, wv, wg, wo, twice; an MLP's three a layer
    assert len(projections) == 2 + 2 * 5 + 3 * 3
    jaxpr = jax.make_jaxpr(jax.grad(make_fused_lm_loss(model, n_chunks=4)))(
        params, tokens).jaxpr
    transposed = {eqn.outvars[0].aval.shape for eqn in _equations(jaxpr)
                  if eqn.primitive.name == "transpose"}
    kernels = set(projections.values())
    assert kernels & transposed == (set() if written else kernels)
