"""A model whose layers differ (Trinity-Mini's ``afmoe``): the routed
layer that is told which experts it holds, the layered ``TransformerLM``
against a plain float32 reference through the compiled train step, and
the sums the step keeps on the device."""

import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu import telemetry
from horovod_tpu.models import (TransformerConfig, TransformerLM,
                                make_fused_lm_loss)
from horovod_tpu.models.transformer import (MOE_DEVICE_SUMS, Attention,
                                            dense_causal_attention)
from horovod_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import weights  # noqa: E402
from chipbench.references import afmoe_train as reference  # noqa: E402
from chipbench.references import precision  # noqa: E402
from tools.sum_by_owner_probe import stood as _sum_as_it_stood  # noqa: E402

from test_smallthinker import _grouped_products  # noqa: E402

T, M, F, E, K = 48, 16, 8, 16, 4


def _layer(key, held, first=0, router=None):
    """Seeded weights of one routed layer holding ``held`` experts from
    ``first``; the whole layer's experts are those of ``held=E``."""
    ks = jax.random.split(key, 5)
    full = {"router": jax.random.normal(ks[1], (M, E)) / math.sqrt(M),
            "wi_gate": jax.random.normal(ks[2], (E, M, F)) / math.sqrt(M),
            "wi_up": jax.random.normal(ks[3], (E, M, F)) / math.sqrt(M),
            "wo": jax.random.normal(ks[4], (E, F, M)) / math.sqrt(F)}
    if router is not None:
        full["router"] = router
    x = jax.random.normal(ks[0], (T, M))
    share = {k: v if k == "router" else v[first:first + held]
             for k, v in full.items()}
    return x, share


def _apply(x, p, first=0, bias=None):
    """The routing step and the experts that take it, in a row, as
    Trinity's layer calls them: ``(y, counts, tokens_per_expert)``."""
    w, idx, by_expert, _ = moe.route(
        x, p["router"], K, expert_bias=bias, route_scale=2.826)
    y, counts = moe.routed_experts_apply(
        x, w, idx, p["wi_gate"], p["wi_up"], p["wo"], num_experts=E,
        first_expert=first)
    return y, counts, by_expert


def _one_hot_form(x, p, first=0, bias=None):
    """Every held expert on every token, times the router's weight for
    it or zero: the dense form, O(held) FLOPs a token."""
    w, idx = moe.score_top_k_routing(
        x, p["router"], jnp.zeros((E,)) if bias is None else bias, K,
        route_scale=2.826)
    held = p["wi_gate"].shape[0]
    weight = jnp.sum(jnp.where(
        idx[:, :, None] == first + jnp.arange(held), w[:, :, None], 0.0), 1)
    hidden = jax.nn.silu(jnp.einsum("tm,emf->etf", x, p["wi_gate"])) \
        * jnp.einsum("tm,emf->etf", x, p["wi_up"])
    return jnp.einsum("etf,efm,te->tm", hidden, p["wo"], weight)


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("held,first", [(E, 0), (4, 0), (4, 8)])
def test_routed_layer_is_the_one_hot_form(held, first):
    x, p = _layer(jax.random.PRNGKey(0), held, first)
    y, counts, by_expert = jax.jit(lambda x, p: _apply(x, p, first))(x, p)
    _close(y, _one_hot_form(x, p, first))
    assert int(counts[0]) == T * K and int(counts[2]) == 0
    assert int(by_expert.sum()) == T * K \
        and int(by_expert[first:first + held].sum()) == int(counts[1])
    assert int(counts[1]) == (T * K if held == E else int(counts[1])) \
        and 0 < int(counts[1]) <= T * K
    # gradients, the router's through the weights among them
    grads = jax.grad(lambda x, p: jnp.sum(_apply(x, p, first)[0] ** 2),
                     argnums=(0, 1))(x, p)
    want = jax.grad(lambda x, p: jnp.sum(_one_hot_form(x, p, first) ** 2),
                    argnums=(0, 1))(x, p)
    jax.tree.map(lambda a, b: _close(a, b, 1e-4), grads, want)
    assert float(jnp.linalg.norm(grads[1]["router"])) > 0


def test_the_eight_shares_add_up_to_the_whole_layer():
    """The routed parts of all the shares, each told other experts,
    equal the uncut layer (a shared expert, which every chip computes
    alike, is outside the routed layer and counted once by the model);
    and against the benchmark's plain reference."""
    x, whole = _layer(jax.random.PRNGKey(1), E)
    shares = [_layer(jax.random.PRNGKey(1), 2, first)[1]
              for first in range(0, E, 2)]
    total, held = 0, 0
    for i, p in enumerate(shares):
        y, counts, _ = _apply(x, p, 2 * i)
        total, held = total + y, held + int(counts[1])
        assert int(counts[2]) == 0
    assert held == T * K                # every assignment on one share
    _close(total, _apply(x, whole)[0])
    _close(total, _one_hot_form(x, whole))
    einsum, _ = precision.products("float32")
    config = {"num_experts_per_tok": K, "route_scale": 2.826}
    _close(total, reference.routed_experts(config, einsum, x, whole,
                                           jnp.zeros((E,)), 0)[0])


@pytest.mark.parametrize("case", ["all_choices_held", "all_on_one_expert"])
def test_dropless_under_the_worst_routing(case, monkeypatch):
    """Every token's choices on held experts (the held experts get
    every assignment there is, several times the buffer's rows), and
    every token on ONE held expert (a group larger than the buffer):
    further passes through the buffer, nothing dropped, and the result
    and its gradients are the dense form's."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)    # tiny sizes: tiny tiles
    held = 4 if case == "all_choices_held" else 1
    direction = jnp.ones((M,)) / math.sqrt(M)
    push = jnp.zeros((E,))
    if case == "all_choices_held":       # K == held: all choices land here
        push = push.at[:held].set(40.0)
    else:                                # expert 0 is in every token's top K
        push = push.at[0].set(40.0).at[1:].set(20.0)
    router = jnp.outer(direction, push)
    x, p = _layer(jax.random.PRNGKey(2), held, router=router)
    x = jnp.abs(x) + 0.1                 # so x . direction > 0
    y, counts, _ = jax.jit(_apply)(x, p)
    want_held = T * K if case == "all_choices_held" else T
    rows = moe.held_buffer_rows(T * K, held, E)
    passes = -(-want_held // rows)
    assert passes > 1       # the first from what it kept, the rest again
    assert [int(c) for c in counts] == [T * K, want_held, 0, passes,
                                        passes - 1]
    _close(y, _one_hot_form(x, p))
    grads = jax.grad(lambda x, p: jnp.sum(_apply(x, p)[0] ** 2),
                     argnums=(0, 1))(x, p)
    want = jax.grad(lambda x, p: jnp.sum(_one_hot_form(x, p) ** 2),
                    argnums=(0, 1))(x, p)
    jax.tree.map(lambda a, b: _close(a, b, 2e-4), grads, want)


def test_expert_bias_enters_the_selection_only():
    x, p = _layer(jax.random.PRNGKey(3), E)
    bias = jnp.zeros((E,)).at[5].set(10.0)      # expert 5 always chosen
    w, idx = moe.score_top_k_routing(x, p["router"], bias, K)
    assert bool(jnp.all(jnp.any(idx == 5, axis=-1)))
    scores = jax.nn.sigmoid(x @ p["router"])
    picked = jnp.take_along_axis(scores, idx, -1)
    _close(w, picked / picked.sum(-1, keepdims=True))
    grad = jax.grad(lambda b: jnp.sum(_apply(x, p, bias=b)[0]))(bias)
    assert float(jnp.abs(grad).max()) == 0.0


def test_expert_bias_moves_toward_the_experts_that_got_fewer():
    """The update of the training loop: down for an expert over the
    mean load, up for one under it, none at the mean; repeated on a
    fixed router it takes a favoured expert's load back to the mean."""
    bias = jnp.asarray([0.5, 0.0, -0.25, 0.0])
    got = moe.updated_expert_bias(bias, jnp.asarray([9, 3, 6, 6]), 0.001)
    _close(got, [0.499, 0.001, -0.25, 0.0], 1e-7)
    x, p = _layer(jax.random.PRNGKey(6), E)
    bias = jnp.zeros((E,)).at[5].set(0.5)        # chosen by nearly all
    step = jax.jit(lambda bias: (
        lambda n: (moe.updated_expert_bias(bias, n, 0.01), n))(
            _apply(x, p, bias=bias)[2]))
    loads = []
    for _ in range(100):
        bias, by_expert = step(bias)
        loads.append(by_expert)
    mean = T * K / E

    def off_balance(n):
        return float(jnp.abs(n - mean).sum())

    assert int(loads[0][5]) > 3 * mean and int(loads[-1][5]) < 2 * mean
    assert off_balance(loads[-1]) < off_balance(loads[0]) / 2


# ---------------------------------------------------------------------------
# the way back to the tokens: each owner's sum of its buffer rows

def _owners_and_rows(topk, seed=0, owners=40, width=384):
    """A table of rows, and owners that each hold some of their
    ``topk`` slots (a valid slot names a row of its own, the others an
    index out of range, as the layer makes them)."""
    rng = np.random.default_rng(seed)
    valid = rng.random((owners, topk)) < 0.4
    n_rows = int(valid.sum()) + 16          # and rows nobody holds
    rows = rng.standard_normal((n_rows, width)).astype(np.float32)
    slot = np.full((owners, topk), owners * topk, np.int32)
    slot[valid] = rng.permutation(n_rows)[:int(valid.sum())]
    return rows, slot, valid


def _count(jaxpr, primitive):
    return sum(eqn.primitive.name == primitive for eqn in jaxpr.eqns)


@pytest.mark.parametrize("mapped", [False, True])
@pytest.mark.parametrize("over_budget", [False, True])
@pytest.mark.parametrize("topk", [1, 2, 6, 8, 9])
def test_sum_by_owner_is_each_owners_sum(topk, over_budget, mapped,
                                         monkeypatch):
    """Against a loop over the owners, whatever form the shapes ask
    for: a slot table that would cut a tile, a table over the budget
    (three column pieces of one lane tile here), under ``vmap``."""
    rows, slot, valid = _owners_and_rows(topk)
    if over_budget:
        monkeypatch.setattr(moe, "_TABLE_BYTES", rows.shape[0] * 128 * 4)
    want = np.zeros((slot.shape[0], rows.shape[1]), np.float64)
    for owner, (slots, held) in enumerate(zip(slot, valid)):
        for row in slots[held]:
            want[owner] += rows[row]
    def fn(*args):       # traced anew under this case's budget
        return moe._sum_by_owner(*args)

    pieces = 3 if over_budget else 1
    jaxpr = jax.make_jaxpr(fn)(rows, slot, valid).jaxpr
    assert _count(jaxpr, "gather") == pieces
    assert _count(jaxpr, "concatenate") == (pieces > 1)
    if mapped:
        got = jax.jit(jax.vmap(fn, in_axes=(0, None, None)))(
            jnp.stack([rows, 2 * rows]), slot, valid)
        assert got.dtype == jnp.float32
        _close(got[0], want, 1e-6)
        _close(got[1], 2 * want, 1e-6)
    else:
        got = jax.jit(fn)(rows, slot, valid)
        assert got.dtype == jnp.float32 and got.shape == want.shape
        _close(got, want, 1e-6)


@pytest.mark.parametrize("topk, width", [(8, 384), (16, 384), (1, 1)])
def test_whole_tiles_under_the_budget_keep_the_form_they_had(topk, width):
    """Trinity's shapes (top-8, a table under the budget) and the
    ``(n, 1)`` use for the routing weights: the jaxpr is the one it
    was, one gather and one sum, nothing padded, turned or joined."""
    rows, slot, valid = _owners_and_rows(topk, width=width)
    now = jax.make_jaxpr(moe._sum_by_owner)(rows, slot, valid)
    assert str(now) == str(jax.make_jaxpr(_sum_as_it_stood)(
        rows, slot, valid))
    for primitive in ("pad", "concatenate", "transpose"):
        assert _count(now.jaxpr, primitive) == 0
    assert _count(now.jaxpr, "gather") == 1


@pytest.mark.parametrize("n_rows, width, itemsize, want", [
    (20480, 2048, 2, [(0, 2048)]),                      # Trinity: 84 MB
    (20480, 2560, 2, [(0, 2560)]),                      # 105 MB fits
    (30720, 2048, 2, [(0, 1024), (1024, 2048)]),        # 126 MB does not
    (30720, 2560, 2, [(0, 1280), (1280, 2560)]),        # SmallThinker
    (30720, 2560, 4, [(0, 640), (640, 1280), (1280, 1920), (1920, 2560)]),
    (20480, 1, 4, [(0, 1)]),                            # the weights
    (10 ** 7, 200, 4, [(0, 128), (128, 200)]),          # a tile at least
])
def test_column_pieces_are_whole_lane_tiles_under_the_budget(
        n_rows, width, itemsize, want):
    got = moe._column_pieces(n_rows, width, itemsize)
    assert got == want
    assert all(first % 128 == 0 for first, _ in got)
    if n_rows < 10 ** 7:
        assert all(n_rows * (last - first) * itemsize <= moe._TABLE_BYTES
                   for first, last in got)


# ---------------------------------------------------------------------------
# the model

CONFIG = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 24,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 64, "sliding_window": 8, "num_hidden_layers": 5,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention",
                                               "sliding_attention"],
    "num_dense_layers": 1, "num_experts": 4, "num_experts_per_tok": 4,
    "published": {"num_experts": 16}, "num_shared_experts": 1,
    "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826,
    "load_balance_coeff": 0.001, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "mup_enabled": True,
    "tie_word_embeddings": False, "initializer_range": 0.02,
    "remat_policy": "dots_flash", "cross_entropy_chunks": 4,
}
WORKLOAD = {"seq_len": 32, "optimizer": {
    "name": "adamw", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.999,
    "eps": 1e-8, "weight_decay": 1e-4}}


def _program_config(dtype=jnp.float32, **changes):
    from chipbench.adapters import afmoe_train

    cfg = afmoe_train.program_config(CONFIG, WORKLOAD)
    return dataclasses.replace(cfg, dtype=dtype, **changes)


def test_model_trains_through_the_compiled_step_as_the_reference(
        hvd_shutdown):
    """Loss and gradients of the model through
    ``make_compiled_train_step`` against the plain reference in
    float32, on seeded weights: two steps' losses, the first gradient
    (read back from AdamW's first moment) leaf by leaf, every expert
    layer's expert_bias after the two steps' updates, and the sums the
    step kept on the device."""
    key = weights.seed_key(7)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    spec = reference.param_spec(CONFIG)
    model = TransformerLM(_program_config())
    shapes = dict(jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t), tokens))
    assert weights.shapes(spec) == weights.shapes(shapes.pop("params"))
    aux_spec = reference.aux_spec(CONFIG)
    assert weights.shapes(aux_spec) == weights.shapes(shapes)

    einsum, _ = precision.products("float32")
    params = weights.make(key, spec)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: reference.batch_loss(CONFIG, einsum, p, tokens))(params)
    found = reference.follow(CONFIG, WORKLOAD, key, tokens, 2)

    hvd.init()
    loss_fn = make_fused_lm_loss(model, n_chunks=4, with_state=True)
    assert loss_fn.device_sums == MOE_DEVICE_SUMS
    step = hvd.make_compiled_train_step(
        loss_fn, optax.adamw(1e-3, weight_decay=1e-4), has_aux=True)
    state = step.init_state(weights.make(key, spec),
                            aux=weights.make(key, aux_spec))
    before = {n: telemetry.counter_total(n) for n in MOE_DEVICE_SUMS}
    state, loss = step(state, tokens)
    assert abs(float(loss) - float(want_loss)) < 2e-5
    got = jax.tree.map(lambda m: m / 0.1, state["opt_state"][0].mu)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-3, atol=2e-6,
            err_msg=jax.tree_util.keystr(path))
    state, loss2 = step(state, tokens)
    assert abs(float(loss2) - found["losses"][1]) < 5e-5
    assert float(loss2) < float(loss)
    biases = jax.tree.leaves(state["aux"])
    for got, want in zip(biases, jax.tree.leaves(found["aux"]), strict=True):
        np.testing.assert_array_equal(np.asarray(got), want)
    assert len(biases) == 4 and all(
        0 < float(jnp.abs(b).max()) <= 0.002001 for b in biases)
    # 2 steps x 64 tokens x 4 choices x 4 expert layers, a quarter held
    delta = {n: telemetry.counter_total(n) - before[n]
             for n in MOE_DEVICE_SUMS}
    assert delta[MOE_DEVICE_SUMS[0]] == 2 * 64 * 4 * 4
    assert 0.15 < delta[MOE_DEVICE_SUMS[1]] / delta[MOE_DEVICE_SUMS[0]] < 0.35
    assert delta[MOE_DEVICE_SUMS[2]] == 0
    # one pass a routed layer a step, none whose forward ran again
    assert delta[MOE_DEVICE_SUMS[3]] == 2 * 4 and delta[MOE_DEVICE_SUMS[4]] == 0
    assert MOE_DEVICE_SUMS[1] in hvd.metrics()


@pytest.mark.parametrize("policy, a_layer", [("dots_flash", 9), ("dots", 9),
                                             ("full", 12)])
def test_replay_of_a_normed_routed_layer_runs_no_grouped_product(
        policy, a_layer):
    """This model norms the routed layer's output, so its backward pass
    reads that output again.  Wherever a remat policy keeps the dense
    products it keeps the routed layer's by name (the first pass's gate
    and up products, the layer's output): the gradient's program holds
    the forward's three grouped products a layer and the six gradients,
    none run again (15 a layer before the names: 3 + 3 replayed + 9);
    ``full`` keeps nothing and replays the three."""
    model = TransformerLM(_program_config(remat=True, remat_policy=policy))
    tokens = jnp.zeros((2, 32), jnp.int32)
    variables = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t), tokens)
    state = {k: v for k, v in variables.items() if k != "params"}

    def loss(params):
        return jnp.sum(model.apply({"params": params, **state}, tokens))

    def products(fn):       # outside the loops over further passes
        return _grouped_products(
            jax.make_jaxpr(fn)(variables["params"]).jaxpr)[0]

    layers = products(loss) // 3
    assert layers == 4 and products(jax.grad(loss)) == a_layer * layers


def test_fp8_control_is_far_from_the_reference():
    key = weights.seed_key(7)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    sound = reference.follow(CONFIG, WORKLOAD, key, tokens, 1)
    control = reference.follow(CONFIG, WORKLOAD, key, tokens, 1, "fp8")
    gap = max(abs(control["grad_norms"][k] - v) / v
              for k, v in sound["grad_norms"].items() if v > 0)
    assert gap > 0.02


def _attention_out(layer_type, rope_on_full=False, seq=24):
    cfg = _program_config(rope_on_full_attention=rope_on_full)
    module = Attention(cfg, dense_causal_attention, layer_type=layer_type)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, seq, 32))
    from horovod_tpu.models.transformer import rope_angles

    angles = jnp.asarray(rope_angles(16, seq, 10000.0))
    params = module.init(jax.random.PRNGKey(5), x, angles)
    return module, params, x, angles


def test_sliding_differs_from_full_exactly_where_the_window_binds():
    """With rotary positions on both kinds, a sliding layer and a full
    one (same weights) agree on the first ``sliding_window`` positions
    and differ on every later one."""
    sliding, params, x, angles = _attention_out("sliding_attention", True)
    full, _, _, _ = _attention_out("full_attention", True)
    a = sliding.apply(params, x, angles)
    b = full.apply(params, x, angles)
    gap = np.abs(np.asarray(a - b)).max(axis=(0, 2))
    assert gap[:8].max() < 1e-6 and gap[8:].min() > 1e-6


def test_full_layer_has_no_positional_signal():
    """A full_attention layer's output does not change with the rotary
    angles when rotary is off for it, and does when it is on."""
    full, params, x, angles = _attention_out("full_attention", False)
    np.testing.assert_array_equal(
        np.asarray(full.apply(params, x, angles)),
        np.asarray(full.apply(params, x, angles * 0 + 1.0)))
    with_rope, _, _, _ = _attention_out("full_attention", True)
    assert float(jnp.abs(with_rope.apply(params, x, angles)
                         - full.apply(params, x, angles)).max()) > 1e-4


def test_mistral_model_keeps_its_tree_and_its_loss():
    """``TransformerLM`` as the ``mistral7b-l2`` configuration builds it
    (rehearsal sizes): the parameter tree its fixed reference expects,
    and the reference's loss."""
    from chipbench.adapters import lm_train
    from chipbench.references import lm_train as mistral_reference

    with open(os.path.join(REPO, "chipbench", "configs",
                           "mistral7b-l2.json")) as f:
        config = json.load(f)
    config.update(config.pop("rehearsal"))
    workload = {"seq_len": 64}
    cfg = dataclasses.replace(lm_train.program_config(config, workload),
                              dtype=jnp.float32)
    assert cfg.layer_types is None and cfg.tie_word_embeddings \
        and cfg.rms_norm_eps == 1e-6 and cfg.head_dim == 16
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    spec = mistral_reference.param_spec(config)
    shapes = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t)["params"], tokens)
    assert weights.shapes(spec) == weights.shapes(shapes)
    assert set(shapes) == {"embed", "layers", "ln_final"}
    params = weights.make(weights.seed_key(3), spec)
    einsum, _ = precision.products("float32")
    want = mistral_reference.batch_loss(config, einsum, params, tokens)
    loss_fn = make_fused_lm_loss(model, n_chunks=4)
    assert loss_fn.device_sums == ()
    assert abs(float(loss_fn(params, tokens)) - float(want)) < 2e-5


def test_untied_head_and_eps_are_keys_of_the_plain_model():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                            d_ff=48, max_seq_len=16, dtype=jnp.float32,
                            tie_word_embeddings=False, rms_norm_eps=1e-5)
    model = TransformerLM(cfg)
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    assert params["lm_head"].shape == (64, 32)
    x, head = model.apply({"params": params}, tokens, pre_logits=True)
    assert head is not params["embed"] and head.shape == (64, 32)
    with pytest.raises(ValueError, match="layer_types"):
        TransformerLM(dataclasses.replace(cfg, sandwich_norm=True)).init(
            jax.random.PRNGKey(0), tokens)


@pytest.mark.parametrize("changes", [{"score_func": "softmax"},
                                     {"route_norm": False}])
def test_only_the_published_router_is_built(changes):
    """The keys mirror ``config.json``; the routed layer has one kind
    of router and refuses the others."""
    with pytest.raises(ValueError, match="sigmoid"):
        TransformerLM(_program_config(**changes)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32))


def test_without_its_state_the_model_routes_with_a_zero_bias():
    """``{"params": ...}`` alone: no bias, nothing updated; with the
    collection and a bias that favours the absent experts the held
    experts get nothing."""
    model = TransformerLM(_program_config())
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    params, state = variables["params"], {
        k: v for k, v in variables.items() if k != "params"}
    assert all(float(jnp.abs(b).max()) == 0 for b in jax.tree.leaves(state))
    plain = make_fused_lm_loss(model, n_chunks=4)(params, tokens)
    loss, new = make_fused_lm_loss(model, n_chunks=4, with_state=True)(
        params, state, tokens)
    assert float(plain) == float(loss)
    assert jax.tree.structure(new) == jax.tree.structure(state)
    assert all(0 < float(jnp.abs(b).max()) <= 0.001001
               for b in jax.tree.leaves(new))
    away = jax.tree.map(lambda b: b.at[..., :4].set(-2.0), state)
    from horovod_tpu.ops import device_sums

    with device_sums.collecting() as found:
        model.apply({"params": params, **away}, tokens)
    assert int(found[MOE_DEVICE_SUMS[1]]) == 0 < int(
        found[MOE_DEVICE_SUMS[0]])


def test_layers_stack_by_the_shortest_period():
    """Depth is a scan: the published 2 dense + 30 expert layers' kinds
    would be one period of ... no shorter than the pattern allows."""
    from horovod_tpu.models.transformer import _shortest_period

    s, f = "sliding_attention", "full_attention"
    assert _shortest_period((s, s, f, s) * 3) == ((s, s, f, s), 3)
    assert _shortest_period((s, s)) == ((s,), 2)
    assert _shortest_period((s, f, s)) == ((s, f, s), 1)
    cfg = _program_config(n_layers=9, layer_types=(s,) + (s, s, f, s) * 2)
    shapes = jax.eval_shape(
        lambda t: TransformerLM(cfg).init(jax.random.PRNGKey(0), t),
        jnp.zeros((1, 32), jnp.int32))["params"]
    assert shapes["periods"]["layer_2"]["moe"]["wi_gate"].shape \
        == (2, 4, 32, 24)
    assert set(shapes["periods"]) == {f"layer_{i}" for i in range(4)}


def test_device_sums_add_up_across_ranks_and_hook_the_stacks():
    """Four ranks under ``hvd.run``, each its own rows: the step's
    device-side sums are summed over the ranks (one ``psum`` of three
    scalars), a read between steps fetches them, the state that every
    rank gets back holds the same accumulators, and the layered model's
    two stacks pass the in-backward gradient hook like ``layers``;
    every layer's expert_bias moved, by at most three updates."""
    model = TransformerLM(_program_config(remat=False))
    loss_fn = make_fused_lm_loss(model, n_chunks=4, with_state=True)
    params, aux = jax.device_get([
        weights.make(weights.seed_key(9), spec)
        for spec in (reference.param_spec(CONFIG),
                     reference.aux_spec(CONFIG))])

    def fn():
        step = hvd.make_compiled_train_step(loss_fn, optax.adamw(1e-3),
                                            has_aux=True)
        state = step.init_state(params, aux=aux)
        tokens = np.asarray(jax.random.randint(
            jax.random.PRNGKey(hvd.rank()), (2, 32), 0, 64))
        reduced = [telemetry.counter_total(n) for n in (
            telemetry.STEP_GRAD_REDUCE_BYTES_FAMILY,
            telemetry.STEP_GRAD_REDUCE_IN_BACKWARD_BYTES_FAMILY)]
        for _ in range(3):
            state, loss = step(state, tokens)
        reduced = [telemetry.counter_total(n) - before for n, before in zip((
            telemetry.STEP_GRAD_REDUCE_BYTES_FAMILY,
            telemetry.STEP_GRAD_REDUCE_IN_BACKWARD_BYTES_FAMILY), reduced)]
        moved = max(float(np.abs(b).max())
                    for b in jax.tree.leaves(jax.device_get(state["aux"])))
        return (float(loss), jax.device_get(state["device_sums"]), reduced,
                [telemetry.counter_total(n) for n in MOE_DEVICE_SUMS], moved)

    outs = hvd.run(fn, np=4)
    # 3 steps x 4 ranks x 64 tokens x 4 choices x 4 expert layers
    want = 3 * 4 * 64 * 4 * 4
    stacked = sum(leaf.size * 4 for name in ("dense_layers", "periods")
                  for leaf in jax.tree.leaves(params[name]))
    for loss, sums, reduced, counters, moved in outs:
        assert np.isfinite(loss) and 0 < moved <= 0.003001
        assert sums[MOE_DEVICE_SUMS[0]].tolist() == [0, want]
        assert sums[MOE_DEVICE_SUMS[2]].tolist() == [0, 0]
        assert sums[MOE_DEVICE_SUMS[3]].tolist() == [0, 3 * 4 * 4]
        assert sums[MOE_DEVICE_SUMS[4]].tolist() == [0, 0]
        assert 0.15 * want < sums[MOE_DEVICE_SUMS[1]][1] < 0.35 * want
        assert reduced[1] == 3 * stacked and reduced[0] > reduced[1]
    # whoever read last saw all of it, once
    assert max(c[0] for _, _, _, c, _ in outs) == want


def test_accumulator_carries_into_its_high_word():
    from horovod_tpu.ops import device_sums

    total = jnp.asarray([0, 2**32 - 5], jnp.uint32)
    total = device_sums.accumulate(total, jnp.int32(9))
    assert total.tolist() == [1, 4]
    assert device_sums.zeros(("a",))["a"].tolist() == [0, 0]
    device_sums.add("outside_a_step", 1)         # no collector: nothing
