"""A looped model (Ouro's LoopLM): one stack of layers run
``total_ut_steps`` times over the same weights, the final norm and an
exit gate after every pass, the expected loss over the exits.  The
program against the benchmark's plain float32 reference, the tie of the
loop to an unlooped model, the exit distribution, the refusals, and the
compiled step on one and on two ranks."""

import dataclasses
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
from horovod_tpu.models import (TransformerConfig, TransformerLM, lm_loss,
                                make_fused_lm_loss)
from horovod_tpu.models import transformer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import weights  # noqa: E402
from chipbench.references import looped_lm_train as reference  # noqa: E402
from chipbench.references import precision  # noqa: E402

PASSES, LAYERS, SEQ = 4, 2, 32
CONFIG = {
    "hidden_size": 32, "intermediate_size": 48, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 8, "vocab_size": 64,
    "num_hidden_layers": LAYERS, "layer_types": ["full_attention"] * LAYERS,
    "sliding_window": None, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "tie_word_embeddings": False, "initializer_range": 0.02,
    "total_ut_steps": PASSES, "exit_entropy_coeff": 0.05,
    "remat_policy": "full", "cross_entropy_chunks": 4,
}
WORKLOAD = {"seq_len": SEQ, "optimizer": {
    "name": "adamw", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.999,
    "eps": 1e-8, "weight_decay": 1e-4}}
TOKENS = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, 64)


def _program_config(**changes):
    from chipbench.adapters import looped_lm_train

    cfg = looped_lm_train.program_config(CONFIG, WORKLOAD)
    return dataclasses.replace(cfg, dtype=jnp.float32, **changes)


def _params(seed=7):
    return weights.make(weights.seed_key(seed), reference.param_spec(CONFIG))


def _close(got, want, tol=2e-5, what=""):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol,
                               atol=tol / 10, err_msg=what)


@pytest.fixture(scope="module")
def both():
    """(loss, gradients) of the program and of the reference on the
    same seeded weights, each leaf under its path."""
    params = _params()
    einsum, _ = precision.products("float32")
    want = jax.value_and_grad(
        lambda p: reference.batch_loss(CONFIG, einsum, p, TOKENS))(params)
    got = jax.value_and_grad(make_fused_lm_loss(
        TransformerLM(_program_config()), n_chunks=4))(params, TOKENS)

    def flat(loss, grads):
        return {"loss": loss, **{
            jax.tree_util.keystr(path): leaf for path, leaf
            in jax.tree_util.tree_leaves_with_path(grads)}}
    return flat(*got), flat(*want)


LEAVES = ["loss"] + sorted(weights.shapes(reference.param_spec(CONFIG)))


def test_reference_tree_is_the_programs():
    shapes = jax.eval_shape(
        lambda t: TransformerLM(_program_config()).init(
            jax.random.PRNGKey(0), t)["params"], TOKENS)
    assert weights.shapes(reference.param_spec(CONFIG)) \
        == weights.shapes(shapes)
    # the stack's weights once, whatever the number of passes
    assert shapes["loop"]["periods"]["layer_0"]["mlp"]["wo"][
        "kernel"].shape == (LAYERS, 48, 32)
    assert shapes["early_exit_gate"]["kernel"].shape == (32, 1)
    assert len(LEAVES) == 17


@pytest.mark.parametrize("leaf", LEAVES)
def test_program_is_the_reference(both, leaf):
    """The loss and every gradient leaf of the program against the
    plain reference (Python loops over passes and layers, no scan)."""
    got, want = both
    _close(got[leaf], want[leaf], what=leaf)
    assert float(jnp.linalg.norm(want[leaf])) > 0


def test_one_pass_is_todays_model():
    """``total_ut_steps=1`` is the ``layer_types`` model of before: its
    tree has no loop and no gate, its loss is the plain cross-entropy;
    and one pass's exit distribution is 1 with no entropy and no
    gradient to the gate."""
    cfg = _program_config(total_ut_steps=1)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(3), TOKENS)["params"]
    assert set(params) == {"embed", "lm_head", "ln_final", "periods"}
    assert model.device_sums == ()
    loss_fn = make_fused_lm_loss(model, n_chunks=4)
    assert not hasattr(loss_fn, "step_counts")
    logits = model.apply({"params": params}, TOKENS)
    _close(loss_fn(params, TOKENS), lm_loss(logits[:, :-1], TOKENS[:, 1:]))

    gates = jax.random.normal(jax.random.PRNGKey(4), (1, 2, SEQ))
    nll = jax.random.uniform(jax.random.PRNGKey(5), (1, 2, SEQ))

    def objective(g):
        p, entropy = transformer.exit_distribution(g)
        return jnp.sum(p * nll - 0.05 * entropy), (p, entropy)

    (value, (p, entropy)), to_gate = jax.value_and_grad(
        objective, has_aux=True)(gates)
    assert np.array_equal(p, np.ones_like(p)) and not entropy.any()
    assert not to_gate.any() and float(value) == float(jnp.sum(nll))


class Unlooped(nn.Module):
    """``passes`` stacks of DISTINCT weights one after the other, each
    with its final norm: what a looped model is when every pass has a
    copy of the stack to itself."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, pre_logits=True):
        cfg = self.cfg
        emb = self.param("embed", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (cfg.vocab_size, cfg.d_model), jnp.float32)
        angles = jnp.asarray(transformer.rope_angles(
            cfg.head_dim, cfg.max_seq_len, cfg.rope_theta))[:tokens.shape[1]]
        x, states = emb[tokens].astype(cfg.dtype), []
        for t in range(cfg.total_ut_steps):
            x = transformer.LoopPass(
                cfg, transformer.dense_causal_attention,
                name=f"copy_{t}")(x, angles)
            states.append(x)
        states = jnp.stack(states)
        gates = nn.Dense(1, param_dtype=jnp.float32,
                         name="early_exit_gate")(states)[..., 0]
        return (states, gates), head


def test_loop_is_an_unlooped_model_with_copied_weights():
    """The tie of the loop to the model: ``passes x layers`` distinct
    layers whose weights are ``passes`` copies of the looped model's
    give the same loss, and their gradients summed over the copies are
    the looped model's."""
    cfg = _program_config(remat=False)
    params = _params(11)
    copied = {k: v for k, v in params.items() if k != "loop"}
    copied.update({f"copy_{t}": params["loop"] for t in range(PASSES)})
    loss, grads = jax.value_and_grad(make_fused_lm_loss(
        TransformerLM(cfg), n_chunks=4))(params, TOKENS)
    want, by_copy = jax.value_and_grad(make_fused_lm_loss(
        Unlooped(cfg), n_chunks=4))(copied, TOKENS)
    _close(loss, want, 1e-6)
    summed = jax.tree.map(lambda *g: sum(g), *(
        by_copy[f"copy_{t}"] for t in range(PASSES)))
    jax.tree.map(_close, grads["loop"], summed)
    # every copy has a gradient of its own: the sum is of four terms
    first = jax.tree.leaves(by_copy["copy_0"])
    last = jax.tree.leaves(by_copy[f"copy_{PASSES - 1}"])
    assert all(float(jnp.abs(a - b).max()) > 0 for a, b in zip(first, last))
    for name in ("embed", "lm_head", "early_exit_gate"):
        jax.tree.map(_close, grads[name], by_copy[name])


@pytest.mark.parametrize("passes", [1, 2, 4, 7])
@pytest.mark.parametrize("scale", [1.0, 40.0])
def test_exit_distribution_sums_to_one(passes, scale):
    """Every token's exit distribution sums to 1 (gates at +-40 put all
    of it on one pass and keep the entropy finite), the expected exit
    pass lies in [1, passes], and it is the reference's distribution."""
    gates = scale * jax.random.normal(jax.random.PRNGKey(passes),
                                      (passes, 3, 50), jnp.float32)
    p, entropy = transformer.exit_distribution(gates)
    _close(jnp.sum(p, axis=0), jnp.ones((3, 50)), 1e-6)
    assert float(p.min()) >= 0 and np.isfinite(np.asarray(entropy)).all()
    assert float(entropy.min()) > -1e-6 \
        and float(entropy.max()) <= np.log(passes) + 1e-6
    expected = jnp.sum(p * jnp.arange(1, passes + 1)[:, None, None], axis=0)
    assert 1 - 1e-6 <= float(expected.min()) \
        and float(expected.max()) <= passes + 1e-6
    want, want_log = reference.exit_distribution(gates)
    _close(p, want, 1e-6)
    _close(entropy, -jnp.sum(want * want_log, axis=0), 1e-5)


@pytest.mark.parametrize("policy", [None, "dots", "dots_flash"])
def test_every_remat_policy_gives_the_same(policy):
    """``full`` (what the others are compared with), no remat, ``dots``
    and ``dots_flash``: the same loss and gradients."""
    params = _params(13)

    def run(**changes):
        return jax.value_and_grad(make_fused_lm_loss(
            TransformerLM(_program_config(**changes)), n_chunks=4))(
                params, TOKENS)

    want = run(remat=True, remat_policy="full")
    got = run(remat=False) if policy is None \
        else run(remat=True, remat_policy=policy)
    jax.tree.map(lambda a, b: _close(a, b, 1e-6), got, want)


@pytest.mark.parametrize("changes, decode, match", [
    ({}, True, "no KV-cache path"),
    ({"num_experts": 4, "expert_top_k": 2}, False, "no routed experts"),
    ({"layer_types": None, "sandwich_norm": False}, False,
     "belongs to a model with layer_types"),
])
def test_what_a_looped_model_refuses(changes, decode, match):
    model = TransformerLM(_program_config(**changes))
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(lambda t: model.init(jax.random.PRNGKey(0), t,
                                            decode=decode), TOKENS)


def test_logits_are_the_last_passes():
    """Without ``pre_logits`` a looped model gives the logits of its
    last pass (``early_exit_threshold`` 1.0: no pass is left early)."""
    model = TransformerLM(_program_config())
    params = _params(17)
    (states, gates), head = model.apply({"params": params}, TOKENS,
                                        pre_logits=True)
    assert states.shape == (PASSES, 2, SEQ, 32) \
        and gates.shape == (PASSES, 2, SEQ)
    _close(model.apply({"params": params}, TOKENS),
           jnp.einsum("bsm,vm->bsv", states[-1], head), 1e-6)


def _loop_counters():
    return {n: telemetry.counter_total(n) for n in (
        *transformer.loop_device_sums(PASSES),
        transformer.LOOP_LAYER_APPLICATIONS)}


def test_trains_through_the_compiled_step_as_the_reference(hvd_shutdown):
    """Two steps of AdamW through ``make_compiled_train_step`` follow
    the reference's; the sums made on the device say that the exit
    masses add up to the tokens, and the host counts ``layers x
    passes`` applications a step call."""
    found = reference.follow(CONFIG, WORKLOAD, weights.seed_key(7), TOKENS,
                             2)
    _, p = reference.batch_loss(CONFIG, precision.products("float32")[0],
                                _params(), TOKENS, with_exits=True)
    hvd.init()
    loss_fn = make_fused_lm_loss(TransformerLM(_program_config()),
                                 n_chunks=4)
    assert loss_fn.device_sums == transformer.loop_device_sums(PASSES)
    step = hvd.make_compiled_train_step(
        loss_fn, optax.adamw(1e-3, weight_decay=1e-4))
    state = step.init_state(_params())
    before = _loop_counters()
    state, loss = step(state, TOKENS)
    assert abs(float(loss) - found["losses"][0]) < 2e-5
    one = {n: v - before[n] for n, v in _loop_counters().items()}
    scored = 2 * (SEQ - 1)
    masses = [one[transformer.loop_exit_mass_sum(t)]
              for t in range(1, PASSES + 1)]
    assert one[transformer.LOOP_TOKENS_SUM] == scored
    assert one[transformer.LOOP_LAYER_APPLICATIONS] == LAYERS * PASSES
    # each mass is kept in steps of 2**-8
    want = np.asarray(jnp.sum(p[:, :, :-1], axis=(1, 2)))
    np.testing.assert_allclose(masses, want, atol=2.0 ** -8)
    assert abs(sum(masses) - scored) <= PASSES * 2.0 ** -9
    expected_pass = sum(t * m for t, m in enumerate(masses, 1)) / scored
    assert 1 <= expected_pass <= PASSES
    state, loss2 = step(state, TOKENS)
    assert abs(float(loss2) - found["losses"][1]) < 5e-5
    assert float(loss2) < float(loss)
    two = _loop_counters()
    assert two[transformer.LOOP_LAYER_APPLICATIONS] \
        - before[transformer.LOOP_LAYER_APPLICATIONS] == 2 * LAYERS * PASSES
    assert transformer.loop_exit_mass_sum(1) in hvd.metrics()


def test_two_ranks_reduce_every_leaf_once():
    """Two virtual ranks under ``hvd.run``, each its own rows: the
    update is the mean of the ranks' own gradients; a step reduces as
    many bytes as the parameters have (the stack's gradient ONCE, not
    once a pass), none of them inside the backward pass, where a leaf
    used four times is not yet whole; the sums made on the device add
    up over the ranks."""
    params = jax.device_get(_params(19))
    loss_fn = make_fused_lm_loss(TransformerLM(_program_config()),
                                 n_chunks=4)
    rows = [np.asarray(jax.random.randint(jax.random.PRNGKey(40 + r),
                                          (2, SEQ), 0, 64))
            for r in range(2)]
    own = [jax.grad(loss_fn)(params, r) for r in rows]
    want = jax.tree.map(lambda p, a, b: p - (a + b) / 2, params, *own)
    families = (telemetry.STEP_GRAD_REDUCE_BYTES_FAMILY,
                telemetry.STEP_GRAD_REDUCE_IN_BACKWARD_BYTES_FAMILY)

    def fn():
        step = hvd.make_compiled_train_step(loss_fn, optax.sgd(1.0))
        state = step.init_state(params)
        before = [telemetry.counter_total(n) for n in families]
        state, loss = step(state, rows[hvd.rank()])
        reduced = [telemetry.counter_total(n) - b
                   for n, b in zip(families, before)]
        return (float(loss), jax.device_get(state["params"]), reduced,
                telemetry.counter_total(transformer.LOOP_TOKENS_SUM),
                sum(telemetry.counter_total(transformer.loop_exit_mass_sum(t))
                    for t in range(1, PASSES + 1)))

    outs = hvd.run(fn, np=2)
    parameter_bytes = sum(leaf.size * 4 for leaf in jax.tree.leaves(params))
    for loss, got, reduced, _, _ in outs:
        assert np.isfinite(loss)
        jax.tree.map(lambda a, b: _close(a, b, 1e-5), got, want)
        assert reduced == [parameter_bytes, 0]
    # whoever read last saw both ranks' tokens, once
    tokens = max(o[3] for o in outs)
    assert tokens == 2 * 2 * (SEQ - 1)
    assert abs(max(o[4] for o in outs) - tokens) <= PASSES * 2.0 ** -8
