"""A model whose router reads the layer's input before attention
(SmallThinker): the routing step and the experts that take it, the
softmax router and its balance loss, ReGLU experts, 7 query heads a
key/value head through the flash kernels, the layered ``TransformerLM``
against a plain float32 reference through the compiled train step, and
Trinity-Mini's layer left bit for bit as it was."""

import dataclasses
import math
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu import telemetry
from horovod_tpu.models import TransformerLM, make_fused_lm_loss
from horovod_tpu.models.transformer import (
    MOE_AUX_LOSS_SUM, MOE_DEVICE_SUMS, MOE_MAX_EXPERT_TOKENS_SUM,
    _with_remat, dense_causal_attention)
from horovod_tpu.ops.pallas_kernels import flash_attention
from horovod_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import weights  # noqa: E402
from chipbench.adapters import smallthinker_train as adapter  # noqa: E402
from chipbench.references import precision  # noqa: E402
from chipbench.references import smallthinker_train as reference  # noqa: E402

T, M, F, E, K = 48, 16, 8, 16, 6


def _layer(key, held=E, first=0, router=None):
    """Seeded weights of one routed layer holding ``held`` experts from
    ``first``; the whole layer's experts are those of ``held=E``."""
    ks = jax.random.split(key, 5)
    full = {"router": jax.random.normal(ks[1], (M, E)),
            "wi_gate": jax.random.normal(ks[2], (E, M, F)) / math.sqrt(M),
            "wi_up": jax.random.normal(ks[3], (E, M, F)) / math.sqrt(M),
            "wo": jax.random.normal(ks[4], (E, F, M)) / math.sqrt(F)}
    if router is not None:
        full["router"] = router
    x = jax.random.normal(ks[0], (T, M))
    return x, {k: v if k == "router" else v[first:first + held]
               for k, v in full.items()}


def _apply(x, p, first=0, routed_on=None):
    """The softmax routing of ``routed_on`` (``x`` itself without it)
    and the ReGLU experts on ``x`` with it."""
    w, idx, by_expert, mean_probs = moe.route(
        x if routed_on is None else routed_on, p["router"], K,
        score_func="softmax")
    y, counts = moe.routed_experts_apply(
        x, w, idx, p["wi_gate"], p["wi_up"], p["wo"], num_experts=E,
        first_expert=first, activation="relu")
    return y, counts, by_expert, mean_probs


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


# ---------------------------------------------------------------------------
# the routing step and the experts that take it

def test_softmax_over_the_selected_is_softmax_over_all_renormalised():
    x, p = _layer(jax.random.PRNGKey(0))
    w, idx, by_expert, mean_probs = moe.route(x, p["router"], K,
                                              score_func="softmax")
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top, top_idx = jax.lax.top_k(probs, K)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(top_idx))
    _close(w, top / top.sum(-1, keepdims=True), 1e-6)
    _close(w.sum(-1), jnp.ones((T,)), 1e-6)
    _close(mean_probs, probs.mean(0), 1e-6)
    assert int(by_expert.sum()) == T * K and mean_probs.shape == (E,)
    with pytest.raises(ValueError, match="expert_bias"):
        moe.route(x, p["router"], K, score_func="softmax",
                  expert_bias=jnp.zeros((E,)))
    with pytest.raises(ValueError, match="score_func"):
        moe.route(x, p["router"], K, score_func="tanh")


def test_the_four_shares_add_up_to_the_uncut_references_layer():
    """The routed parts of the four shares (``first_expert_held`` 0,
    1/4, 2/4, 3/4 of the router's experts), each routed alike, equal
    the plain reference's layer holding every expert."""
    x, whole = _layer(jax.random.PRNGKey(1))
    total, held = 0, 0
    for first in range(0, E, E // 4):
        y, counts, _, _ = _apply(
            x, _layer(jax.random.PRNGKey(1), E // 4, first)[1], first)
        total, held = total + y, held + int(counts[1])
        assert int(counts[2]) == 0
    assert held == T * K                # every assignment on one share
    _close(total, _apply(x, whole)[0])
    einsum, _ = precision.products("float32")
    config = {"moe_num_active_primary_experts": K}
    w, idx, _ = reference.routing(config, einsum, x, whole["router"])
    _close(total, reference.routed_experts(
        einsum, x, whole, reference.held_weights(w, idx, 0, E)))


@pytest.mark.parametrize("activation", ["relu", "silu"])
def test_the_experts_activation_is_a_parameter(activation):
    x, p = _layer(jax.random.PRNGKey(2), 4, 4)
    w, idx, _, _ = moe.route(x, p["router"], K, score_func="softmax")
    y, _ = moe.routed_experts_apply(
        x, w, idx, p["wi_gate"], p["wi_up"], p["wo"], num_experts=E,
        first_expert=4, activation=activation)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[activation]
    weight = jnp.sum(jnp.where(
        idx[:, :, None] == 4 + jnp.arange(4), w[:, :, None], 0.0), 1)
    hidden = act(jnp.einsum("tm,emf->etf", x, p["wi_gate"])) \
        * jnp.einsum("tm,emf->etf", x, p["wi_up"])
    _close(y, jnp.einsum("etf,efm,te->tm", hidden, p["wo"], weight))


def test_balance_loss_is_one_under_balance_and_grows_with_skew():
    """``E sum_e f_e P_e``: exactly 1.0 when every expert gets the same
    share (whatever the probabilities), over 1 when the experts the
    router favours are the ones that got the tokens; its gradient
    reaches the router through ``P_e`` only."""
    even = jnp.full((E,), T * K // E, jnp.int32)
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(3), (E,)))
    assert float(moe.load_balance_loss(even, probs)) == pytest.approx(1.0)
    # a router pushed onto expert 0: it gets a token from every row
    x, p = _layer(jax.random.PRNGKey(3))
    skewed = p["router"].at[:, 0].set(0.0)
    x = x.at[:, 0].set(4.0)
    skewed = skewed.at[0, 0].set(2.0)
    _, _, by_expert, mean_probs = moe.route(x, skewed, K,
                                            score_func="softmax")
    assert int(by_expert[0]) == T
    assert float(moe.load_balance_loss(by_expert, mean_probs)) > 1.5

    def aux(router, counts=None):
        _, _, by_expert, mean_probs = moe.route(x, router, K,
                                                score_func="softmax")
        return moe.load_balance_loss(
            by_expert if counts is None else counts, mean_probs)

    grad = jax.grad(aux)(skewed)
    assert float(jnp.abs(grad).max()) > 0
    # the counts carry no gradient: with them held fixed it is the same
    np.testing.assert_array_equal(
        np.asarray(grad), np.asarray(jax.grad(
            lambda r: aux(r, jax.lax.stop_gradient(by_expert)))(skewed)))
    # ... and it is d/dW_r of E sum_e f_e mean_t softmax(x W_r)_e
    share = by_expert / by_expert.sum()
    _close(grad, jax.grad(lambda r: E * jnp.sum(
        share * jax.nn.softmax(x @ r, -1).mean(0)))(skewed), 1e-5)


def test_dropless_under_a_router_skewed_onto_one_expert(monkeypatch):
    """Every token on ONE held expert (a group larger than the buffer):
    further passes through the buffer, nothing dropped, the result and
    its gradients the dense form's."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)    # tiny sizes: tiny tiles
    push = jnp.zeros((E,)).at[0].set(40.0).at[1:].set(
        jnp.linspace(20.0, 10.0, E - 1))
    router = jnp.outer(jnp.ones((M,)) / math.sqrt(M), push)
    x, p = _layer(jax.random.PRNGKey(4), 1, router=router)
    x = jnp.abs(x) + 0.1                 # so x . direction > 0

    def dense(x, p):
        w, idx, _, _ = moe.route(x, p["router"], K, score_func="softmax")
        weight = jnp.sum(jnp.where(idx == 0, w, 0.0), 1)
        hidden = jax.nn.relu(x @ p["wi_gate"][0]) * (x @ p["wi_up"][0])
        return (hidden @ p["wo"][0]) * weight[:, None]

    y, counts, _, _ = jax.jit(_apply)(x, p)
    assert [int(c) for c in counts[:3]] == [T * K, T, 0]
    assert T > moe.held_buffer_rows(T * K, 1, E)     # a second pass
    _close(y, dense(x, p))
    grads = jax.grad(lambda x, p: jnp.sum(_apply(x, p)[0] ** 2),
                     argnums=(0, 1))(x, p)
    want = jax.grad(lambda x, p: jnp.sum(dense(x, p) ** 2),
                    argnums=(0, 1))(x, p)
    jax.tree.map(lambda a, b: _close(a, b, 1e-4), grads, want)


# the way back to the tokens (``moe._sum_by_owner``) in every form the
# shapes can ask for: 64 tokens of width 256 (two lane tiles), 9 of 32
# experts held
_WAY_BACK = dict(tokens=64, width=256, ffn=8, experts=32, held=9)


def _taken_routing(topk, skewed, seed):
    """``(x, weights, idx, wi_gate, wi_up, wo)``: a routing the layer
    TAKES.  ``skewed``: every choice of every token on a held expert,
    so the held assignments are all there are and pass three times
    through a buffer sized for a balanced router; else uniform over
    the 32 experts (one pass)."""
    d = _WAY_BACK
    rng = np.random.default_rng(seed)
    among = d["held"] if skewed else d["experts"]
    idx = np.argsort(rng.random((d["tokens"], among)), axis=1)[:, :topk]
    f32 = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    return (f32(d["tokens"], d["width"]),
            rng.random((d["tokens"], topk)).astype(np.float32) + 0.1,
            idx.astype(np.int32),
            f32(d["held"], d["width"], d["ffn"]) / math.sqrt(d["width"]),
            f32(d["held"], d["width"], d["ffn"]) / math.sqrt(d["width"]),
            f32(d["held"], d["ffn"], d["width"]) / math.sqrt(d["ffn"]))


def _per_token_form(x, weights, idx, wi_gate, wi_up, wo, activation="relu"):
    """Every held expert on every token, times the weight the taken
    routing gives it or zero."""
    weight = jnp.sum(jnp.where(
        idx[:, :, None] == jnp.arange(wi_gate.shape[0]),
        weights[:, :, None], 0.0), 1)
    hidden = moe.ACTIVATIONS[activation](
        jnp.einsum("tm,emf->etf", x, wi_gate)) \
        * jnp.einsum("tm,emf->etf", x, wi_up)
    return jnp.einsum("etf,efm,te->tm", hidden, wo, weight)


@pytest.mark.parametrize("mapped", [False, True])
@pytest.mark.parametrize("skewed", [False, True])
@pytest.mark.parametrize("over_budget", [False, True])
@pytest.mark.parametrize("topk", [1, 2, 6, 8, 9])
def test_the_way_back_holds_its_values_and_gradients_in_every_form(
        topk, over_budget, skewed, mapped, monkeypatch):
    """``routed_experts_apply`` against the per-token form: the values
    and the gradients to ``x``, the routing weights and the three
    expert matrices, for slot tables that fill a tile (8), cut one
    (2, 6, 9) or have one column, a buffer under and over the budget of
    one gather (two column pieces), one pass and three, alone and under
    the ``vmap`` the one-device compiled step wraps the loss in."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    args = _taken_routing(topk, skewed, seed=topk)
    n = _WAY_BACK["tokens"] * topk
    buffer_rows = moe.held_buffer_rows(n, _WAY_BACK["held"],
                                       _WAY_BACK["experts"])

    def held_part(*args):       # traced anew under this case's budget
        return moe.routed_experts_apply(
            *args, num_experts=_WAY_BACK["experts"], activation="relu")

    def gathers():
        return str(jax.make_jaxpr(lambda *a: held_part(*a))(*args)).count(
            "gather[")

    if over_budget:
        whole = gathers()
        monkeypatch.setattr(moe, "_TABLE_BYTES", buffer_rows * 128 * 4)
        assert len(moe._column_pieces(buffer_rows, 256, 4)) == 2
        # one more in the first pass and in the loop of further passes
        assert gathers() == whole + 2
    y, counts = jax.jit(held_part)(*args)
    n_held = int(np.sum(args[2] < _WAY_BACK["held"]))
    passes = 3 if skewed else 1      # and those the backward runs again
    assert [int(c) for c in counts] == [n, n_held, 0, passes, passes - 1]
    assert -(-n_held // buffer_rows) == passes

    def loss(fn):
        def scalar(x, weights, wi_gate, wi_up, wo):
            return jnp.sum(fn(x, weights, args[2], wi_gate, wi_up, wo) ** 2)
        return scalar

    inexact = (args[0], args[1]) + args[3:]
    got_fn = jax.value_and_grad(
        loss(lambda *a: held_part(*a)[0]), argnums=tuple(range(5)))
    want_fn = jax.value_and_grad(loss(_per_token_form),
                                 argnums=tuple(range(5)))
    if mapped:      # over x and the weights, as over a rank axis
        axes = (0, 0, None, None, None)
        inexact = (jnp.stack([inexact[0], 0.5 * inexact[0]]),
                   jnp.stack([inexact[1], inexact[1][::-1]])) + inexact[2:]
        got_fn, want_fn = (jax.vmap(f, in_axes=axes)
                           for f in (got_fn, want_fn))
    else:
        _close(y, _per_token_form(*args), 1e-4)
    got, want = jax.jit(got_fn)(*inexact), jax.jit(want_fn)(*inexact)
    jax.tree.map(lambda a, b: _close(
        a, b, 2e-4 * float(jnp.max(jnp.abs(b)))), got, want)
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in got[1])


# the first pass hands its backward pass what it computed
# (``moe._first_pass_gradients``); further passes are recomputed there

def _held_part(activation, dtype=jnp.float32):
    """``(x, weights, idx, wi_gate, wi_up, wo) -> y``: the layer on
    ``_WAY_BACK``'s sizes with its products in ``dtype``."""
    def held_part(x, weights, idx, *experts):
        cast = lambda a: a.astype(dtype)    # noqa: E731
        return moe.routed_experts_apply(
            cast(x), weights, idx, *map(cast, experts),
            num_experts=_WAY_BACK["experts"], activation=activation)[0]
    return held_part


def _gradients(fn, args):
    """Of ``sum(fn(...) ** 2)`` to ``x``, the routing weights and the
    three expert matrices."""
    x, weights, idx, *experts = args
    return jax.jit(jax.grad(
        lambda x, weights, *experts: jnp.sum(
            fn(x, weights, idx, *experts).astype(jnp.float32) ** 2),
        argnums=tuple(range(5))))(x, weights, *experts)


@pytest.fixture
def first_pass_recomputed(monkeypatch):
    """Inside: the layer as it stood before its first pass kept
    anything, that pass differentiated as a whole like every other."""
    def recomputed(rows_held, topk, activation, gate, up, x, order, slot,
                   sizes, weights, wi_gate, wi_up, wo, ct):
        _, vjp = jax.vjp(
            lambda x, *rest: moe._pass_of_experts(
                rows_held, topk, activation, 0, jnp.zeros_like(ct), x,
                order, slot, sizes, *rest)[0],
            x, weights, wi_gate, wi_up, wo)
        return vjp(ct)

    def switch():
        monkeypatch.setattr(moe, "_first_pass_gradients", recomputed)
        moe._held_experts.cache_clear()     # rules that close over it

    yield switch
    monkeypatch.undo()
    moe._held_experts.cache_clear()


@pytest.mark.parametrize("skewed", [False, True])
@pytest.mark.parametrize("activation", ["silu", "relu"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gradients_from_the_kept_products_are_the_oracles(
        dtype, activation, skewed, monkeypatch, first_pass_recomputed):
    """The gradients of ``routed_experts_apply`` to ``x``, the routing
    weights and the three expert matrices against the per-token form in
    float32, for a routing that fits one pass (all of it from what the
    pass kept) and one that needs three (the first from what it kept,
    two recomputed): float32 to 1e-5 of the largest entry; bf16 products
    within the 2e-2 that the layer differentiated pass by pass, as it
    stood, keeps too."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    args = _taken_routing(6, skewed, seed=11)
    want = _gradients(
        lambda *a: _per_token_form(*a, activation=activation), args)
    tol = 1e-5 if dtype == "float32" else 2e-2
    for as_it_stood in [False, True][:1 + (dtype == "bfloat16")]:
        if as_it_stood:
            first_pass_recomputed()
        got = _gradients(_held_part(activation, jnp.dtype(dtype)), args)
        for g, w in zip(got, want, strict=True):
            assert g.dtype == w.dtype and g.shape == w.shape
            scale = float(jnp.max(jnp.abs(w)))
            assert scale > 0
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=tol, atol=tol * scale)


def _grouped_products(jaxpr, looped=False):
    """``[outside, inside]``: the ``ragged_dot``s of a jaxpr and of
    whatever it calls, outside and inside the ``while`` loops (the loop
    over further passes, whose length only the device knows)."""
    found = [0, 0]
    for eqn in jaxpr.eqns:
        found[looped] += eqn.primitive.name == "ragged_dot_general"
        inner = looped or eqn.primitive.name == "while"
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found = [a + b for a, b in zip(
                        found, _grouped_products(sub, inner))]
    return found


def test_a_one_pass_backward_runs_the_six_gradients_alone(
        first_pass_recomputed):
    """Counted in the jaxpr: the forward's three grouped products (and
    three in the loop over further passes); in the backward pass the six
    gradients where the pass recomputed takes nine, as each further pass
    in its loop still does and as the first did before it kept its gate
    and up products."""
    args = _taken_routing(6, False, seed=12)
    inexact = args[:2] + args[3:]

    def counts():
        fn = lambda x, w, *experts: _held_part("relu")(  # noqa: E731
            x, w, args[2], *experts)
        y, vjp = jax.vjp(fn, *inexact)
        return (_grouped_products(jax.make_jaxpr(fn)(*inexact).jaxpr),
                _grouped_products(jax.make_jaxpr(vjp)(y).jaxpr))

    assert counts() == ([3, 3], [6, 9])
    first_pass_recomputed()
    assert counts() == ([3, 3], [9, 9])


class _HeldPart(nn.Module):
    """The layer as a module, for ``_with_remat``."""
    @nn.compact
    def __call__(self, *args):
        return _held_part("silu")(*args)


@pytest.mark.parametrize("ranks", [1, 2])
@pytest.mark.parametrize("skewed", [False, True])
@pytest.mark.parametrize("policy, products", [
    ("dots_flash", 9), ("dots", 9), ("full", 12)])
def test_remat_keeps_the_first_pass_products_where_it_keeps_the_dense(
        policy, products, skewed, ranks, monkeypatch):
    """Under ``nn.remat`` with each policy of ``_with_remat`` and under
    the ``vmap`` over a rank axis of 1 and 2 that the one-device
    compiled step wraps the loss in, one pass and three: the gradients
    are the un-rematted layer's, and outside the loop over further
    passes the program holds the forward's three products and the six
    gradients wherever a policy keeps the dense products; ``full`` keeps
    nothing and runs the rule's forward again, three products more."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    args = _taken_routing(6, skewed, seed=13)
    idx = args[2]
    inexact = [jnp.stack([a * (1 + r) for r in range(ranks)])
               for a in args[:2]] + list(args[3:])
    cfg = _program_config(remat=True, remat_policy=policy)
    rematted = _with_remat(_HeldPart, cfg)()

    def grads(fn):
        return jax.vmap(jax.value_and_grad(
            lambda x, w, *experts: jnp.sum(
                fn(x, w, idx, *experts) ** 2), argnums=tuple(range(5))),
            in_axes=(0, 0, None, None, None))

    got_fn = grads(lambda *a: rematted.apply({}, *a))
    want_fn = grads(_held_part("silu"))
    got, want = jax.jit(got_fn)(*inexact), jax.jit(want_fn)(*inexact)
    jax.tree.map(lambda a, b: _close(
        a, b, 1e-6 * float(jnp.max(jnp.abs(b)))), got, want)
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in got[1])
    assert _grouped_products(
        jax.make_jaxpr(got_fn)(*inexact).jaxpr)[0] == products
    assert _grouped_products(
        jax.make_jaxpr(want_fn)(*inexact).jaxpr)[0] == 9


def _parents_routed_layer(x, router_w, expert_bias, wi_gate, wi_up, wo, *,
                          first_expert=0, topk, route_scale=1.0):
    """``routed_experts_apply`` as the parent commit had it: routing and
    experts in one function, the sigmoid router, SwiGLU."""
    T = x.shape[0]
    held, num_experts = wi_gate.shape[0], router_w.shape[-1]
    n = T * topk
    weights, idx = moe.score_top_k_routing(
        x, router_w, expert_bias, topk, route_scale=route_scale)
    tokens_per_expert = jnp.sum(
        idx[:, :, None] == jnp.arange(num_experts), axis=(0, 1),
        dtype=jnp.int32)
    local = idx.reshape(n) - first_expert
    key = jnp.where((local >= 0) & (local < held), local, held)
    rows_held = moe.held_buffer_rows(n, held, num_experts)
    order = jnp.pad(jnp.argsort(key, stable=True).astype(jnp.int32),
                    (0, -n % rows_held))
    mine = key[:, None] == jnp.arange(held)[None]
    sizes = jnp.sum(mine, axis=0, dtype=jnp.int32)
    earlier = jnp.cumsum(mine, axis=0, dtype=jnp.int32) - 1 \
        + (jnp.cumsum(sizes) - sizes)[None]
    slot = jnp.where(key < held, jnp.sum(
        jnp.where(mine, earlier, 0), axis=1), n).reshape(T, topk)
    y, computed = moe._held_experts(rows_held, topk, "silu")(
        x, order, slot, sizes, weights, wi_gate, wi_up, wo)
    n_held = jnp.sum(sizes)
    return y, jnp.stack([jnp.int32(n), n_held, n_held - computed]), \
        tokens_per_expert


def test_trinitys_layer_is_bit_equal_after_the_split():
    """Trinity-Mini's layer calls the routing step and the experts in a
    row: value, counts and every gradient are the unsplit function's,
    bit for bit."""
    x, p = _layer(jax.random.PRNGKey(5), 4, 8)
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(6), (E,))

    def split(x, p):
        w, idx, by_expert, none = moe.route(
            x, p["router"], 4, expert_bias=bias, route_scale=2.826)
        assert none is None
        y, counts = moe.routed_experts_apply(
            x, w, idx, p["wi_gate"], p["wi_up"], p["wo"], num_experts=E,
            first_expert=8)
        return y, counts[:3], by_expert     # the passes came later

    def parent(x, p):
        return _parents_routed_layer(
            x, p["router"], bias, p["wi_gate"], p["wi_up"], p["wo"],
            first_expert=8, topk=4, route_scale=2.826)

    for got, want in zip(jax.jit(split)(x, p), jax.jit(parent)(x, p)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    grads = [jax.jit(jax.grad(lambda x, p: jnp.sum(fn(x, p)[0] ** 2),
                              argnums=(0, 1)))(x, p)
             for fn in (split, parent)]
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), *grads)


# ---------------------------------------------------------------------------
# 7 query heads on 1 key/value head through the flash kernels

@pytest.mark.parametrize("window", [None, 64])
def test_seven_to_one_grouped_heads_through_the_flash_kernels(window):
    """28 query heads on 4 key/value heads (7 to a group, no power of
    two), as the model hands them over (keys and values repeated): the
    kernels in interpret mode against dense attention, value and
    gradients, under the full causal mask and under a window."""
    B, S, H, KV, D = 1, 256, 28, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
    ct = jax.random.normal(ks[3], (B, S, H, D), jnp.float32)

    def through(fn):
        def loss(q, k, v):
            out = fn(q, jnp.repeat(k, H // KV, axis=2),
                     jnp.repeat(v, H // KV, axis=2), window=window)
            return jnp.sum(out * ct), out
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)

    (_, out), grads = through(
        lambda *a, **kw: flash_attention(*a, interpret=True, **kw))
    (_, want), want_grads = through(dense_causal_attention)
    _close(out, want, 2e-3)
    for g, w in zip(grads, want_grads):
        _close(g, w, 5e-3)


# ---------------------------------------------------------------------------
# the model

CONFIG = {
    "hidden_size": 32, "moe_ffn_hidden_size": 24, "head_dim": 16,
    "num_attention_heads": 7, "num_key_value_heads": 1, "vocab_size": 64,
    "num_hidden_layers": 4, "sliding_window_layout": [0, 1, 1, 1],
    "rope_layout": [0, 1, 1, 1], "sliding_window_size": 8,
    "moe_num_primary_experts": 4, "moe_num_active_primary_experts": 6,
    "published": {"moe_num_primary_experts": 16},
    "deployment": {"first_expert_held": 4},
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_theta": 1500000,
    "tie_word_embeddings": False, "initializer_range": 0.02,
    "embedding_initializer_std": 1.0, "router_initializer_std": 0.5,
    "router_aux_loss_coef": 0.05, "remat_policy": "dots_flash",
    "cross_entropy_chunks": 4,
}
WORKLOAD = {"seq_len": 32, "optimizer": {
    "name": "adamw", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.999,
    "eps": 1e-8, "weight_decay": 1e-4}}
AUX_SUMS = MOE_DEVICE_SUMS + (MOE_AUX_LOSS_SUM, MOE_MAX_EXPERT_TOKENS_SUM)


def _program_config(dtype=jnp.float32, **changes):
    cfg = adapter.program_config(CONFIG, WORKLOAD)
    return dataclasses.replace(cfg, dtype=dtype, **changes)


def _tokens(seed=1, rows=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, 32), 0, 64)


def test_the_model_is_the_published_layer():
    cfg = _program_config()
    assert cfg.layer_types == ("full_attention",) + (
        "sliding_attention",) * 3
    assert cfg.score_func == "softmax" and cfg.router_before_attention \
        and cfg.expert_activation == "relu" and not cfg.num_dense_layers \
        and not cfg.num_shared_experts and not cfg.rope_on_full_attention
    model = TransformerLM(cfg)
    assert model.routed_layers == 4 and model.device_sums == AUX_SUMS
    shapes = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t), _tokens())
    assert set(shapes) == {"params"}     # no state beside the parameters
    assert weights.shapes(reference.param_spec(CONFIG)) \
        == weights.shapes(shapes["params"])
    assert shapes["params"]["periods"]["layer_0"]["moe"]["router"].shape \
        == (1, 32, 16)


@pytest.mark.parametrize("remat", [True, False])
def test_model_trains_through_the_compiled_step_as_the_reference(
        hvd_shutdown, remat):
    """Loss (with its balance term) and gradients of the model through
    ``make_compiled_train_step`` against the plain reference in float32
    on seeded weights, with the layers rematerialised and without: two
    AdamW steps' losses, the first gradient (read back from AdamW's
    first moment) leaf by leaf, and the sums the step kept on the
    device."""
    key, tokens = weights.seed_key(7), _tokens()
    spec = reference.param_spec(CONFIG)
    einsum, _ = precision.products("float32")
    (want_loss, seen), want_grads = jax.value_and_grad(
        lambda p: reference.batch_loss(CONFIG, einsum, p, tokens),
        has_aux=True)(weights.make(key, spec))
    aux = [float(v[0]) for v in seen["aux_loss"].values()]
    assert len(aux) == 4 and all(a > 1.0 for a in aux)
    assert float(want_loss) == pytest.approx(
        float(seen["cross_entropy"]) + 0.05 * sum(aux) / 4, abs=1e-6)
    found = reference.follow(CONFIG, WORKLOAD, key, tokens, 2)

    hvd.init()
    loss_fn = make_fused_lm_loss(TransformerLM(_program_config(remat=remat)),
                                 n_chunks=4)
    assert loss_fn.device_sums == AUX_SUMS
    step = hvd.make_compiled_train_step(
        loss_fn, optax.adamw(1e-3, weight_decay=1e-4))
    state = step.init_state(weights.make(key, spec))
    before = {n: telemetry.counter_total(n) for n in AUX_SUMS}
    state, loss = step(state, tokens)
    assert abs(float(loss) - float(want_loss)) < 2e-5
    got = jax.tree.map(lambda m: m / 0.1, state["opt_state"][0].mu)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-3, atol=2e-6,
            err_msg=jax.tree_util.keystr(path))
    # the routers learn from the balance term too
    assert all(float(jnp.abs(layer["moe"]["router"]).max()) > 0
               for layer in got["periods"].values())
    state, loss2 = step(state, tokens)
    assert abs(float(loss2) - found["losses"][1]) < 5e-5
    assert float(loss2) < float(loss)
    # 2 steps x 64 tokens x 6 choices x 4 layers, a quarter held
    delta = {n: telemetry.counter_total(n) - before[n] for n in AUX_SUMS}
    assert delta[MOE_DEVICE_SUMS[0]] == 2 * 64 * 6 * 4
    assert 0.1 < delta[MOE_DEVICE_SUMS[1]] / delta[MOE_DEVICE_SUMS[0]] < 0.4
    assert delta[MOE_DEVICE_SUMS[2]] == 0
    assert delta[MOE_DEVICE_SUMS[3]] == 2 * 4 and delta[MOE_DEVICE_SUMS[4]] == 0
    want_aux = sum(float(v[0]) for seen in found["seen"]
                   for v in seen["aux_loss"].values())
    assert delta[MOE_AUX_LOSS_SUM] == pytest.approx(want_aux, abs=2 / 256)
    assert delta[MOE_MAX_EXPERT_TOKENS_SUM] == sum(
        int(v.max()) for seen in found["seen"]
        for v in seen["counts"].values())


def test_routing_is_a_function_of_the_layers_input_alone():
    """Perturbing every attention weight changes no token's experts in
    the FIRST layer (its router reads the embedding), although it
    changes what the experts are fed; with the router after attention
    the same perturbation moves choices."""
    tokens = _tokens(2)
    params = weights.make(weights.seed_key(11), reference.param_spec(CONFIG))
    shaken = jax.tree.map(lambda a: a, params)
    shaken["periods"]["layer_0"]["attn"] = jax.tree.map(
        lambda a: a + 0.5 * jax.random.normal(jax.random.PRNGKey(8),
                                              a.shape),
        params["periods"]["layer_0"]["attn"])

    def first_layer_choices(p, **changes):
        """idx of layer 0's router, through the model's own block."""
        from horovod_tpu.models.transformer import (LayeredBlock,
                                                    rope_angles)

        cfg = _program_config(**changes)
        seen = {}
        real = moe.routed_experts_apply

        def spy(x, weights, idx, *args, **kwargs):
            seen["idx"], seen["x"] = idx, x
            return real(x, weights, idx, *args, **kwargs)

        layer = jax.tree.map(lambda a: a[0], p["periods"]["layer_0"])
        x = p["embed"][tokens]
        angles = jnp.asarray(rope_angles(16, 32, 1500000.0))
        try:
            moe.routed_experts_apply = spy
            LayeredBlock(cfg, dense_causal_attention, "full_attention",
                         True).apply({"params": layer}, x, angles)
        finally:
            moe.routed_experts_apply = real
        return np.asarray(seen["idx"]), np.asarray(seen["x"])

    idx, fed = first_layer_choices(params)
    idx_shaken, fed_shaken = first_layer_choices(shaken)
    np.testing.assert_array_equal(idx, idx_shaken)
    assert np.abs(fed - fed_shaken).max() > 1e-3
    after, _ = first_layer_choices(params, router_before_attention=False)
    after_shaken, _ = first_layer_choices(shaken,
                                          router_before_attention=False)
    assert (after != after_shaken).any() and (after != idx).any()


@pytest.mark.parametrize("changes, match", [
    ({"score_func": "tanh"}, "score_func"),
    ({"route_norm": False}, "renormalises"),
    ({"expert_activation": "gelu"}, "expert_activation"),
    ({"load_balance_coeff": 0.001}, "auxiliary"),
    ({"score_func": "sigmoid"}, "expert_bias")])
def test_a_router_the_model_does_not_build_is_refused(changes, match):
    with pytest.raises(ValueError, match=match):
        TransformerLM(_program_config(**changes)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32))


def test_two_ranks_agree_with_the_one_rank_mean():
    """Two ranks under ``hvd.run``, each its own rows and its own
    ``f_e`` / ``P_e``: the reduced gradient is the mean of the two
    one-rank gradients, the new sums add up over the ranks."""
    model = TransformerLM(_program_config())
    loss_fn = make_fused_lm_loss(model, n_chunks=4)
    params = jax.device_get(weights.make(
        weights.seed_key(9), reference.param_spec(CONFIG)))
    rows = [np.asarray(_tokens(20 + r)) for r in range(2)]
    alone = [jax.value_and_grad(loss_fn)(params, r) for r in rows]
    want = jax.tree.map(lambda a, b: (a + b) / 2, alone[0][1], alone[1][1])

    def fn():
        step = hvd.make_compiled_train_step(loss_fn, optax.adamw(1e-3))
        state = step.init_state(params)
        state, loss = step(state, rows[hvd.rank()])
        return (float(loss),
                jax.device_get(jax.tree.map(lambda m: m / 0.1,
                                            state["opt_state"][0].mu)),
                jax.device_get(state["device_sums"]))

    for loss, grads, sums in hvd.run(fn, np=2):
        assert loss == pytest.approx(
            (float(alone[0][0]) + float(alone[1][0])) / 2, abs=1e-6)
        jax.tree.map(lambda g, w: np.testing.assert_allclose(
            g, np.asarray(w), rtol=1e-4, atol=1e-7), grads, want)
        assert sums[MOE_DEVICE_SUMS[0]].tolist() == [0, 2 * 64 * 6 * 4]
        # four layers a rank, each a little over 1.0, in steps of 2^-8
        assert 8 * 256 < sums[MOE_AUX_LOSS_SUM][1] < 16 * 256
        assert sums[MOE_MAX_EXPERT_TOKENS_SUM][1] >= 2 * 4 * 64 * 6 // 16
