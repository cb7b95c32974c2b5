"""A model with delta-rule and latent-attention layers (Kimi-Linear):
the chunked delta rule against the token-by-token recurrence, the state
carried across chunk boundaries, the causal convolutions, latent
attention against the plain float32 reference and with its values
filled up against the dense inner at unequal widths, each kind of layer
against the reference, the eight-of-256 share against the uncut layer,
the layered ``TransformerLM`` through the compiled train step, and what
the accepted configurations' models keep."""

import dataclasses
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu import telemetry
from horovod_tpu.models import (MLAAttention, TransformerConfig,
                                TransformerLM, dense_causal_attention,
                                make_fused_lm_loss)
from horovod_tpu.models import kda as kda_module
from horovod_tpu.models.kda import (KDA_DEVICE_SUMS, KDAMixer, KEPT,
                                    kda_chunked)
from horovod_tpu.models.mamba import CausalConv
from horovod_tpu.models.transformer import MOE_DEVICE_SUMS, LayeredBlock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import weights  # noqa: E402
from chipbench.adapters import kimi_linear_train as adapter  # noqa: E402
from chipbench.references import kimi_linear_train as reference  # noqa: E402
from chipbench.references import precision  # noqa: E402

EINSUM, _ = precision.products("float32")
F32 = jnp.float32


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


def _load(name):
    with open(os.path.join(REPO, "chipbench", "configs", name + ".json")) as f:
        config = json.load(f)
    config.update(config.pop("rehearsal"))
    return config


# ---------------------------------------------------------------------------
# the rule

def _rule_inputs(seq, strong, rows=2, heads=3, width=8, seed=0):
    """q and k as the mixer hands them over (l2-normed, q scaled).  The
    decay a position and a channel is log-uniform: in [0.001, 0.1], the
    published regime, where a state outlives many chunks; ``strong``:
    in [0.5, 30], where ``exp(G)`` underflows inside a chunk."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (rows, seq, heads, width)
    q = kda_module.l2norm(jax.random.normal(ks[0], shape, F32)) \
        / np.sqrt(width)
    k = kda_module.l2norm(jax.random.normal(ks[1], shape, F32))
    v = jax.random.normal(ks[2], shape, F32)
    low, high = (0.5, 30.0) if strong else (1e-3, 1e-1)
    g = -jnp.exp(jax.random.uniform(ks[3], shape, F32, np.log(low),
                                    np.log(high)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3], F32))
    return (q, k, v, g, beta), jax.random.normal(ks[5], shape, F32)


@jax.jit
def _recurrence(q, k, v, g, beta):
    return jax.vmap(lambda *row: reference.delta_rule(EINSUM, *row, block=8))(
        q, k, v, g, beta)


@pytest.mark.parametrize("seq, chunk, strong", [
    (32, 8, False), (32, 16, False), (37, 8, False),    # a row that ends
    (32, 8, True), (64, 32, True),                      # inside a chunk
    (5, 16, False),                                     # and inside the first
])
def test_chunked_rule_is_the_recurrence_in_value_and_every_gradient(
        seq, chunk, strong):
    """Float32, to the rounding of sums in another order; with strong
    decays ``G`` falls past -90 inside a chunk, where ``exp`` underflows
    (and ``exp(-G)`` overflows), and everything stays finite, because
    every exponent is a difference <= 0."""
    args, ct = _rule_inputs(seq, strong)
    got, chunks = jax.jit(lambda *a: kda_chunked(*a, chunk=chunk))(*args)
    assert int(chunks) == -(-seq // min(chunk, 8 if seq == 5 else chunk))
    want = _recurrence(*args)
    assert bool(jnp.isfinite(got).all())
    _close(got, want, 1e-5)
    if strong:
        assert float(jnp.cumsum(args[3], axis=1)[:, chunk - 1].min()) < -90
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(kda_chunked(*a, chunk=chunk)[0] * ct),
        argnums=(0, 1, 2, 3, 4)))(*args)
    wants = jax.jit(jax.grad(lambda *a: jnp.sum(_recurrence(*a) * ct),
                             argnums=(0, 1, 2, 3, 4)))(*args)
    for name, got_g, want_g in zip("q k v g beta".split(), grads, wants):
        assert bool(jnp.isfinite(got_g).all()), name
        np.testing.assert_allclose(
            np.asarray(got_g), np.asarray(want_g), rtol=2e-4,
            atol=2e-5 * float(jnp.abs(want_g).max()), err_msg=name)


def _no_carry(w_v, w_k, k_end, decay):
    """``kda._starts`` with the pass over the chunks left out."""
    rows, heads, chunks, _, width = w_v.shape
    return jnp.zeros((chunks, rows, heads, w_k.shape[-1], width), F32)


def test_the_state_carried_across_chunk_boundaries_matters(monkeypatch):
    """The same rule with every chunk started from a zero state (the
    fault the chip's comparison is made to catch) is far from the
    recurrence past the first chunk, and equal to it inside it."""
    args, _ = _rule_inputs(32, False)
    want = _recurrence(*args)
    monkeypatch.setattr(kda_module, "_starts", _no_carry)
    cut, _ = kda_chunked(*args, chunk=8)
    _close(cut[:, :8], want[:, :8], 1e-5)
    assert float(jnp.abs(cut[:, 8:] - want[:, 8:]).max()) \
        > 0.1 * float(jnp.abs(want[:, 8:]).max())


def test_heads_in_groups_compute_what_all_at_once_do(monkeypatch):
    """Three heads one after another (a limit of one row of positions)
    against all at once: the same values and gradients."""
    args, ct = _rule_inputs(32, False)

    def run(limit):
        monkeypatch.setattr(kda_module, "GROUP_POSITIONS", limit)
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(kda_chunked(*a, chunk=8)[0] * ct),
            argnums=(0, 1, 2, 3, 4)))(*args)

    assert kda_module._group_size(2, 32, 3, 64) == 1 \
        and kda_module._group_size(2, 32, 3, 1 << 17) == 3 \
        and kda_module._group_size(2, 8192, 32, 1 << 17) == 8
    (one, grads_one), (whole, grads_whole) = run(64), run(1 << 17)
    _close(one, whole, 2e-5)
    for got, want in zip(grads_one, grads_whole):
        _close(got, want, 1e-5)


def test_a_chunk_length_that_is_no_power_of_two_is_refused():
    args, _ = _rule_inputs(24, False)
    with pytest.raises(ValueError, match="kda_chunk_size"):
        kda_chunked(*args, chunk=12)


def test_the_convolutions_are_causal():
    """Position t of ``silu(conv)`` reads positions t - 3 .. t of its
    own channel: a change at position 9 leaves 0 .. 8 as they were and
    moves 9 .. 12; no bias among the parameters."""
    conv = CausalConv(4, F32, use_bias=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 6), F32)
    params = conv.init(jax.random.PRNGKey(1), x)
    assert set(params["params"]) == {"kernel"}
    moved = conv.apply(params, x.at[0, 9, 2].add(1.0)) - conv.apply(params, x)
    changed = np.argwhere(np.abs(np.asarray(moved)) > 0)
    assert {tuple(c) for c in changed} == {(0, t, 2) for t in range(9, 13)}
    _close(conv.apply(params, x)[0],
           reference.causal_conv_silu(x[0], params["params"]["kernel"]))


# ---------------------------------------------------------------------------
# the layers against the reference

CONFIG = _load("kimi-linear-48b-l5-ep32")
WORKLOAD = {"seq_len": 32, "optimizer": {
    "name": "adamw", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.999,
    "eps": 1e-8, "weight_decay": 1e-4}}
D = CONFIG["hidden_size"]


def _program_config(dtype=F32, **changes):
    cfg = adapter.program_config(CONFIG, WORKLOAD)
    return dataclasses.replace(cfg, dtype=dtype, kda_chunk_size=8, **changes)


def _tokens(seed=1, rows=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, 32), 0,
                              CONFIG["vocab_size"])


def _layer_params(group, layer, seed=3):
    """One layer's seeded weights, the leading (repeats) axis taken
    off; a kda layer's ``A_log`` and ``dt_bias`` in the published
    regime, as the program's initialisers draw them."""
    params = weights.make(weights.seed_key(seed),
                          reference.param_spec(CONFIG))[group][layer]
    params = jax.tree.map(lambda a: a[0], params)
    if "kda" in params:
        drawn = KDAMixer(_program_config()).init(
            jax.random.PRNGKey(seed), jnp.zeros((1, 8, D)))["params"]
        for name in ("A_log", "dt_bias"):
            params["kda"][name] = drawn[name]
        assert float(drawn["A_log"].min()) >= 0 \
            and float(drawn["A_log"].max()) <= np.log(16) + 1e-6
        assert float(jax.nn.softplus(drawn["dt_bias"]).max()) <= 0.1 + 1e-6
    return params


def test_the_kda_mixer_is_the_references():
    """The program's mixer in float32 with chunks of 8 against the
    reference's, whose rule is the recurrence."""
    p = _layer_params("periods", "layer_0")["kda"]
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 32, D), F32)
    got, counts = jax.jit(KDAMixer(_program_config()).apply)({"params": p}, h)
    want = jax.jit(jax.vmap(lambda row: reference.kda_mixer(
        CONFIG, EINSUM, row, p)))(h)
    _close(got, want, 2e-5)
    assert counts.tolist() == [2 * 32, 2 * 4]


def test_latent_attention_is_the_references():
    """``MLAAttention`` (the values filled up to the keys' width for
    the inner) against the reference's scores at the published shape of
    the widths: keys wider than values."""
    p = _layer_params("periods", "layer_3")["attn"]
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 32, D), F32)
    got = jax.jit(MLAAttention(_program_config()).apply)({"params": p}, h)
    want = jax.jit(jax.vmap(lambda row: reference.mla_mixer(
        CONFIG, EINSUM, row, p, 8)))(h)
    _close(got, want, 2e-5)
    assert p["wq"]["kernel"].shape[-1] == 32 \
        and p["wo"]["kernel"].shape[1] == 16       # 16 + 16 against 16


def test_filled_values_equal_the_dense_inner_at_192_and_128():
    """At the PUBLISHED head widths: attention with 192-wide queries and
    keys and 128-wide values, the values filled up with zeros to 192 for
    an inner of one head size and the output's filled columns dropped,
    equals the softmax computed at the two widths directly, exactly in
    the kept columns."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 24, 2, 192), F32)
    k = jax.random.normal(ks[1], (1, 24, 2, 192), F32)
    v = jax.random.normal(ks[2], (1, 24, 2, 128), F32)
    filled = dense_causal_attention(
        q, k, jnp.pad(v, ((0, 0),) * 3 + ((0, 64),)))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(192)
    scores = jnp.where(jnp.tril(jnp.ones((24, 24), bool)), scores, -1e30)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    _close(filled[..., :128], want, 1e-6)
    assert float(jnp.abs(filled[..., 128:]).max()) == 0.0


def test_one_key_part_for_all_heads_and_no_position_in_latent_attention():
    """The shared key part is ONE projection of a token (``kv_a``'s last
    columns, no head axis), and nothing in the layer knows a position:
    permuting the tokens BEFORE a row leaves that row's output as it
    was (a causal softmax over a set of keys), which rotary positions
    would not."""
    cfg = _program_config()
    p = _layer_params("periods", "layer_3")["attn"]
    assert p["kv_a"]["kernel"].shape == (
        D, cfg.kv_lora_rank + cfg.qk_rope_head_dim)
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 32, D), F32)
    order = jnp.concatenate([jax.random.permutation(
        jax.random.PRNGKey(6), 20), jnp.arange(20, 32)])
    apply = jax.jit(MLAAttention(cfg).apply)
    _close(apply({"params": p}, h[:, order])[:, 20:],
           apply({"params": p}, h)[:, 20:], 1e-5)
    # and it is attention: a row does change with an earlier token
    other = apply({"params": p}, h.at[0, 3].add(1.0))
    assert float(jnp.abs(other - apply({"params": p}, h))[0, 25].max()) > 1e-4


@pytest.mark.parametrize("group, layer, kind, routed", [
    ("dense_layers", "layer_0", "kda", False),
    ("periods", "layer_0", "kda", True),
    ("periods", "layer_3", "mla", True)])
def test_the_layer_is_the_references(group, layer, kind, routed):
    """Each kind of layer, with the dense SwiGLU (the leading layer) and
    with the routed experts held here, a zero expert_bias."""
    p = _layer_params(group, layer)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, D), F32)
    cfg = _program_config()
    got, sums = jax.jit(LayeredBlock(
        cfg, TransformerLM(cfg).attention_fn, kind, routed).apply)(
            {"params": p}, x, jnp.zeros((32, 4)))
    bias = jnp.zeros((CONFIG["published"]["num_experts"],), F32)

    def want_row(row):
        mixed = reference.mixer_row(CONFIG, EINSUM, row, p, kind, 8)
        return reference.feed_forward_block(CONFIG, EINSUM, mixed, p, bias)[0]

    _close(got, jax.jit(jax.vmap(want_row))(x), 2e-5)
    assert sums["kda"].tolist() == ([64, 8] if kind == "kda" else [0, 0])
    assert int(sums["counts"][0]) == (64 * 4 if routed else 0)


def test_the_shares_add_up_to_the_uncut_layer():
    """At a small size: the routed parts that the four shares of a
    16-expert layer give (experts 0-3, 4-7, 8-11, 12-15, each through
    the PROGRAM's layer told which experts it holds), with the shared
    expert and the residual counted once, sum to what the uncut
    reference gives for the whole layer with all 16 experts."""
    whole = dict(CONFIG, num_experts=16)
    key = weights.seed_key(11)
    p = jax.tree.map(lambda a: a[0], weights.make(
        key, reference.param_spec(whole))["periods"]["layer_0"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, D), F32)
    bias = jnp.zeros((16,), F32)

    def share(first):
        moe = dict(p["moe"], **{name: p["moe"][name][first:first + 4]
                                for name in ("wi_gate", "wi_up", "wo")})
        cfg = _program_config(first_expert_held=first)
        assert cfg.num_experts == 16 and cfg.num_experts_held == 4
        block = LayeredBlock(cfg, TransformerLM(cfg).attention_fn, "kda",
                             True)
        out, sums = block.apply({"params": dict(p, moe=moe)}, x,
                                jnp.zeros((32, 4)))
        return out, sums["counts"]

    # what every share computes alike: the mixer, the residual, the
    # shared expert.  Take it from a share with its routed part removed
    shares = [share(first) for first in (0, 4, 8, 12)]
    mixed = jax.vmap(lambda row: reference.mixer_row(
        CONFIG, EINSUM, row, p, "kda", 8))(x)
    alike = jax.vmap(lambda row: row + reference._swiglu(
        EINSUM, reference._rms_norm(row, p["ln_mlp"]["scale"],
                                    CONFIG["rms_norm_eps"]),
        p["moe"]["shared"]))(mixed)
    routed = sum(out - alike for out, _ in shares)
    want_whole = jax.jit(jax.vmap(lambda row: reference.feed_forward_block(
        whole, EINSUM, row, p, bias)[0]))(mixed)
    _close(alike + routed, want_whole, 5e-5)
    # every assignment fell on exactly one share
    held = sum(int(counts[1]) for _, counts in shares)
    assert held == int(shares[0][1][0]) == 64 * 4


def test_the_model_is_the_published_layers():
    cfg = _program_config()
    assert cfg.layer_types == ("kda", "kda", "kda", "kda", "mla")
    assert cfg.num_dense_layers == 1 and cfg.score_func == "sigmoid" \
        and cfg.route_scale == 2.446 and not cfg.tie_word_embeddings
    model = TransformerLM(cfg)
    assert model.routed_layers == 4
    assert model.device_sums == MOE_DEVICE_SUMS + KDA_DEVICE_SUMS
    shapes = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t), _tokens())
    assert set(shapes) == {"params", "router_state"}
    assert weights.shapes(reference.param_spec(CONFIG)) \
        == weights.shapes(shapes["params"])
    assert weights.shapes(reference.aux_spec(CONFIG)) \
        == weights.shapes({"router_state": shapes["router_state"]})
    layer = shapes["params"]["periods"]["layer_3"]
    assert "kda" not in layer and "mlp" not in layer
    assert shapes["params"]["dense_layers"]["layer_0"]["kda"]["wq"][
        "kernel"].shape == (1, D, 64)
    with pytest.raises(ValueError, match="KV-cache"):
        jax.eval_shape(lambda p: model.apply({"params": p}, _tokens(),
                                             decode=True), shapes["params"])


@pytest.fixture(scope="module")
def followed():
    """The reference's first gradient and two steps, once for the
    module."""
    key, tokens = weights.seed_key(7), _tokens()
    aux = weights.make(key, reference.aux_spec(CONFIG))
    (want_loss, _), want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.batch_loss(CONFIG, EINSUM, p, tokens, aux),
        has_aux=True))(weights.make(key, reference.param_spec(CONFIG)))
    return want_loss, want_grads, reference.follow(CONFIG, WORKLOAD, key,
                                                   tokens, 2)


@pytest.mark.parametrize("remat", [True, False, "dots"])
def test_model_trains_through_the_compiled_step_as_the_reference(
        hvd_shutdown, followed, remat):
    """Two AdamW steps' losses, the first gradient (read back from
    AdamW's first moment) leaf by leaf, the parameters' change and the
    threaded expert_bias, through ``make_compiled_train_step`` against
    the reference in float32 on seeded weights; with the layers
    rematerialised (``full`` and ``dots``) and without; and the sums the
    step kept on the device."""
    key, tokens = weights.seed_key(7), _tokens()
    spec = reference.param_spec(CONFIG)
    want_loss, want_grads, found = followed
    assert found["losses"][0] == pytest.approx(float(want_loss), abs=1e-6)

    hvd.init()
    policy = {"remat_policy": remat} if isinstance(remat, str) else {}
    loss_fn = make_fused_lm_loss(TransformerLM(_program_config(
        remat=bool(remat), **policy)), n_chunks=4, with_state=True)
    assert loss_fn.device_sums == MOE_DEVICE_SUMS + KDA_DEVICE_SUMS
    step = hvd.make_compiled_train_step(
        loss_fn, optax.adamw(1e-3, weight_decay=1e-4), has_aux=True)
    state = step.init_state(weights.make(key, spec),
                            aux=weights.make(key, reference.aux_spec(CONFIG)))
    before = [telemetry.counter_total(n) for n in KDA_DEVICE_SUMS]
    state, loss = step(state, tokens)
    assert abs(float(loss) - float(want_loss)) < 2e-5
    got = jax.tree.map(lambda m: m / 0.1, state["opt_state"][0].mu)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-3,
            atol=2e-5 * float(jnp.abs(w).max()) + 1e-8,
            err_msg=jax.tree_util.keystr(path))
    state, loss2 = step(state, tokens)
    assert abs(float(loss2) - found["losses"][1]) < 5e-5
    assert float(loss2) < float(loss)
    change = weights.leaf_norms(jax.tree.map(
        lambda a, b: a - b, state["params"], weights.make(key, spec)))
    for leaf, want in found["delta_norms"].items():
        assert float(change[leaf]) == pytest.approx(float(want), rel=2e-3), \
            leaf
    for got_b, want_b in zip(jax.tree.leaves(state["aux"]),
                             jax.tree.leaves(found["aux"])):
        np.testing.assert_array_equal(np.asarray(got_b), np.asarray(want_b))
    assert float(jnp.abs(jax.tree.leaves(state["aux"])[0]).max()) \
        == pytest.approx(2 * CONFIG["load_balance_coeff"])
    # 2 steps x 64 tokens x 4 kda layers, in chunks of 8
    delta = [telemetry.counter_total(n) - b
             for n, b in zip(KDA_DEVICE_SUMS, before)]
    assert delta == [2 * 64 * 4, 2 * 8 * 4]


def test_the_fp8_control_is_far():
    """The reference with every product in fp8, the rule's reads of its
    state among them, stands further from the float32 reference than the
    program does by the first gradient's norms."""
    key, tokens = weights.seed_key(7), _tokens()
    sound = reference.follow(CONFIG, WORKLOAD, key, tokens, 1)
    control = reference.follow(CONFIG, WORKLOAD, key, tokens, 1, "fp8")
    gaps = [abs(control["grad_norms"][leaf] - want) / want
            for leaf, want in sound["grad_norms"].items() if want > 0]
    assert max(gaps) > 0.02
    assert abs(control["losses"][0] - sound["losses"][0]) > 1e-5


def _scans(jaxpr):
    """How many ``scan`` equations a jaxpr holds, its sub-jaxprs'
    among them."""
    total = 0
    for eqn in jaxpr.eqns:
        total += eqn.primitive.name == "scan"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _scans(sub)
    return total


def test_full_remat_keeps_the_scans_named_outputs_and_replays_no_scan():
    """One kda layer under the model's ``full`` policy: the gradient's
    program runs the rule forward once and backward once, each a loop
    over the groups of heads around the recurrence over the chunks (two
    ``scan`` equations each); the replay needs neither the states (kept
    by name) nor the output (kept by name), so it runs no scan.  Without
    the two names the replay runs the forward pair again."""
    from horovod_tpu.models import transformer

    cfg = _program_config(remat=True)
    p = _layer_params("periods", "layer_0")
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, D), F32)

    def scans():
        block = transformer._with_remat(LayeredBlock, cfg, prevent_cse=True)(
            cfg, dense_causal_attention, "kda", True)
        return _scans(jax.make_jaxpr(jax.grad(
            lambda q: block.apply({"params": q}, x, jnp.zeros((32, 4)))[0]
            .sum()))(p).jaxpr)

    assert set(KEPT) == {"kda_out", "kda_states"}
    kept = scans()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(transformer, "KDA_KEPT", ())
        replayed = scans()
    assert (kept, replayed) == (4, 6)


def test_an_unknown_kind_and_a_key_no_layer_reads_are_refused_by_name():
    tokens = _tokens()

    def init(**changes):
        return TransformerLM(_program_config(**changes)).init(
            jax.random.PRNGKey(0), tokens)

    with pytest.raises(ValueError, match="retnet"):
        init(layer_types=("kda", "retnet", "kda", "kda", "mla"))
    with pytest.raises(ValueError, match="kv_lora_rank.*v_head_dim"):
        init(layer_types=("kda",) * 5)
    with pytest.raises(ValueError, match="kda_n_heads.*kda_chunk_size"):
        init(layer_types=("mla",) * 5)
    with pytest.raises(ValueError, match="kda_d_head"):
        init(kda_d_head=None)
    with pytest.raises(ValueError, match="v_head_dim"):
        init(v_head_dim=64)       # wider than the keys
    # and in the plain model, which has no kinds at all
    plain = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                              n_heads=2, d_ff=48, max_seq_len=32,
                              dtype=F32, kda_chunk_size=64)
    with pytest.raises(ValueError, match="kda_chunk_size"):
        TransformerLM(plain).init(jax.random.PRNGKey(0), tokens)


# ---------------------------------------------------------------------------
# the accepted configurations' models

@pytest.mark.parametrize("name, module", [
    ("mistral7b-l2", "lm_train"),
    ("trinity-mini-l5-ep8", "afmoe_train"),
    ("ouro-2.6b-l8", "looped_lm_train"),
    ("smallthinker-21b-l4-ep4", "smallthinker_train"),
    ("granite-4.0-h-micro-l10", "granite_hybrid_train")])
def test_accepted_models_keep_their_trees_and_their_losses(name, module):
    """``TransformerLM`` as each accepted LM configuration builds it
    (rehearsal sizes): the parameter tree its fixed reference expects,
    no new key among the sums it makes on the device, and the
    reference's loss."""
    other = importlib.import_module("chipbench.adapters." + module)
    ref = importlib.import_module("chipbench.references." + module)
    config, workload = _load(name), {"seq_len": 32}
    cfg = dataclasses.replace(other.program_config(config, workload),
                              dtype=F32)
    for key in ("kda_n_heads", "kda_chunk_size", "kv_lora_rank",
                "v_head_dim"):
        assert getattr(cfg, key) is None
    model = TransformerLM(cfg)
    assert not set(model.device_sums) & set(KDA_DEVICE_SUMS)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                config["vocab_size"])
    shapes = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t)["params"], tokens)
    spec = ref.param_spec(config)
    assert weights.shapes(spec) == weights.shapes(shapes)
    params = weights.make(weights.seed_key(3), spec)
    want = jax.jit(lambda p: ref.batch_loss(config, EINSUM, p, tokens))(
        params)
    if isinstance(want, tuple):         # (loss, what the layers saw)
        want = want[0]
    loss_fn = make_fused_lm_loss(model, n_chunks=4)
    assert abs(float(jax.jit(loss_fn)(params, tokens)) - float(want)) < 5e-5
