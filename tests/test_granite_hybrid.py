"""A model with state-space layers (Granite-4.0-H): the chunked
selective scan against the token-by-token recurrence, the state carried
across chunk boundaries, the causal convolution, the mixer and the layer
against the plain float32 reference, the layered ``TransformerLM``
through the compiled train step, the four muP-style multipliers, and
attention at heads of 64 with a scale of 1/64 through the flash
kernels."""

import dataclasses
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
                                chunked_lm_loss, lm_loss, make_fused_lm_loss)
from horovod_tpu.models.mamba import (SSM_DEVICE_SUMS, CausalConv,
                                      Mamba2Mixer, ssd_chunked)
from horovod_tpu.models.transformer import LayeredBlock
from horovod_tpu.ops.pallas_kernels import flash_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import weights  # noqa: E402
from chipbench.adapters import granite_hybrid_train as adapter  # noqa: E402
from chipbench.references import granite_hybrid_train as reference  # noqa: E402
from chipbench.references import precision  # noqa: E402

EINSUM, _ = precision.products("float32")


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


# ---------------------------------------------------------------------------
# the scan

def _scan_inputs(seq, groups=1, rows=2, heads=4, width=8, state=16, seed=0):
    """Inputs in Mamba-2's own regime: dt log-uniform in [0.001, 0.1], A
    in [1, 16], so a state outlives many chunks."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    f32 = jnp.float32
    x = jax.random.normal(ks[0], (rows, seq, heads, width), f32)
    dt = jnp.exp(jax.random.uniform(ks[1], (rows, seq, heads), f32,
                                    np.log(1e-3), np.log(1e-1)))
    a = -jax.random.uniform(ks[2], (heads,), f32, 1.0, 16.0)
    b = jax.random.normal(ks[3], (rows, seq, groups, state), f32)
    c = jax.random.normal(ks[4], (rows, seq, groups, state), f32)
    ct = jax.random.normal(ks[5], (rows, seq, heads, width), f32)
    return (x, dt, a, b, c), ct


@jax.jit
def _recurrence(x, dt, a, b, c):
    return jax.vmap(lambda x, dt, b, c: reference.selective_scan(
        EINSUM, x, dt, a, b, c, block=8))(x, dt, b, c)


@pytest.mark.parametrize("seq, chunk, groups", [
    (64, 16, 1),      # the chunk divides the row
    (50, 16, 1),      # it does not: the last chunk is filled up
    (12, 16, 1),      # a row shorter than one chunk
    (64, 64, 1),      # one chunk: no state is carried
    (40, 8, 2),       # two groups of B and C
])
def test_chunked_scan_is_the_recurrence_in_value_and_every_gradient(
        seq, chunk, groups):
    """float32 on both sides: what differs is the order of the sums
    (a chunk's cumulative sum against a product of decays), 1e-5 of
    values of order 1."""
    inputs, ct = _scan_inputs(seq, groups)

    def through(fn):
        return jax.jit(jax.value_and_grad(
            lambda *args: jnp.sum(fn(*args) * ct), argnums=(0, 1, 2, 3, 4)))(
                *inputs)

    got, got_grads = through(
        lambda *args: ssd_chunked(*args, chunk=chunk)[0])
    want, want_grads = through(_recurrence)
    assert float(got) == pytest.approx(float(want), rel=1e-5, abs=1e-4)
    for g, w in zip(got_grads, want_grads):
        _close(g, w, 5e-5 * float(jnp.abs(w).max()) + 1e-5)
    assert ssd_chunked(*inputs, chunk=chunk)[1] \
        == -(-seq // min(chunk, seq))


def test_the_state_carried_across_chunk_boundaries_matters():
    """Every chunk run alone from a zero state (the scan with its pass
    over the chunks left out) is far from the recurrence after the first
    chunk, and equal to it inside the first."""
    (x, dt, a, b, c), _ = _scan_inputs(64, rows=1)
    whole, _ = ssd_chunked(x, dt, a, b, c, chunk=16)
    alone, _ = ssd_chunked(*(t.reshape((4, 16) + t.shape[2:])
                             for t in (x, dt)), a,
                           *(t.reshape((4, 16) + t.shape[2:])
                             for t in (b, c)), chunk=16)
    alone = alone.reshape(whole.shape)
    _close(whole[:, :16], alone[:, :16], 1e-6)
    gap = jnp.abs(whole[:, 16:] - alone[:, 16:])
    assert float(gap.mean()) > 0.1 * float(jnp.abs(whole[:, 16:]).mean())
    _close(whole, _recurrence(x, dt, a, b, c), 2e-5)


def test_the_convolution_is_causal():
    """Position t reads t - 3 .. t of its own channel and never t + 1;
    the program's is the reference's."""
    conv = CausalConv(4, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 6), jnp.float32)
    params = conv.init(jax.random.PRNGKey(2), x)
    y = conv.apply(params, x)
    later = conv.apply(params, x.at[:, 10].add(1.0))
    np.testing.assert_array_equal(np.asarray(y[:, :10]),
                                  np.asarray(later[:, :10]))
    moved = np.abs(np.asarray(later - y)).sum(axis=(0, 2))
    assert (moved[10:14] > 0).all() and (moved[14:] == 0).all()
    p = params["params"]
    _close(y, jax.vmap(lambda row: reference.causal_conv_silu(
        row, p["kernel"], p["bias"]))(x), 1e-6)


# ---------------------------------------------------------------------------
# the model

CONFIG = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "shared_intermediate_size": 48, "intermediate_size": 48,
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 8,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "mamba_chunk_size": 8,
    "attention_bias": False, "num_local_experts": 0,
    "position_embedding_type": "nope", "vocab_size": 64,
    "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.125, "logits_scaling": 8,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "initializer_range": 0.02, "conv_initializer_std": 0.2887,
    "a_log_initializer_std": 0.75, "remat_policy": "full",
    "cross_entropy_chunks": 4,
}
WORKLOAD = {"seq_len": 32, "optimizer": {
    "name": "adamw", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.999,
    "eps": 1e-8, "weight_decay": 1e-4}}


def _program_config(dtype=jnp.float32, **changes):
    cfg = adapter.program_config(CONFIG, WORKLOAD)
    return dataclasses.replace(cfg, dtype=dtype, **changes)


def _tokens(seed=1, rows=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, 32), 0, 64)


def _layer_params(kind, seed=3):
    """One layer's seeded weights, the leading (repeats) axis taken
    off; the mamba scalars in Mamba-2's own regime, as the program's
    initialisers draw them."""
    params = weights.make(weights.seed_key(seed),
                          reference.param_spec(CONFIG))["periods"]
    layer = jax.tree.map(lambda a: a[0],
                         params["layer_0" if kind == "mamba" else "layer_2"])
    if kind == "mamba":
        drawn = Mamba2Mixer(_program_config()).init(
            jax.random.PRNGKey(seed), jnp.zeros((1, 8, 32)))["params"]
        for name in ("A_log", "dt_bias", "D"):
            layer["mamba"][name] = drawn[name]
        assert float(drawn["A_log"].min()) >= 0 \
            and float(drawn["A_log"].max()) <= np.log(16) + 1e-6
        assert float(jax.nn.softplus(drawn["dt_bias"]).max()) <= 0.1 + 1e-6
    return layer


def test_the_mixer_is_the_references():
    """The program's mixer in float32 with chunks of 8 against the
    reference's, whose scan is the recurrence; to the rounding of the
    scan's sums."""
    p = _layer_params("mamba")["mamba"]
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 32), jnp.float32)
    got, counts = jax.jit(Mamba2Mixer(_program_config()).apply)(
        {"params": p}, h)
    want = jax.jit(jax.vmap(lambda row: reference.mamba_mixer(
        CONFIG, EINSUM, row, p)))(h)
    _close(got, want, 2e-5)
    assert counts.tolist() == [2 * 32, 2 * 4, 0]    # tiny widths: the XLA form


@pytest.mark.parametrize("kind", ["mamba", "attention"])
def test_the_layer_is_the_references(kind):
    """Both kinds of layer with the residual multiplier on both
    branches; the attention layer takes no position encoding and scales
    its scores by ``attention_multiplier``."""
    p = _layer_params(kind)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 32),
                          jnp.float32)
    cfg = _program_config()
    got, sums = jax.jit(LayeredBlock(
        cfg, TransformerLM(cfg).attention_fn,
        "mamba" if kind == "mamba" else "full_attention", False).apply)(
            {"params": p}, x, jnp.zeros((32, 4)))
    want = jax.jit(jax.vmap(lambda row: reference.mlp_tokens(
        CONFIG, EINSUM, reference.mixer_row(CONFIG, EINSUM, row, p, kind, 8),
        p)))(x)
    _close(got, want, 2e-5)
    assert sums["ssm"].tolist() == ([64, 8, 0] if kind == "mamba" else [0] * 3)


def test_the_model_is_the_published_layer():
    cfg = _program_config()
    assert cfg.layer_types == ("mamba", "mamba", "full_attention", "mamba")
    assert not cfg.rope_on_full_attention and cfg.head_dim == 8
    model = TransformerLM(cfg)
    assert model.routed_layers == 0 and model.device_sums == SSM_DEVICE_SUMS
    shapes = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t), _tokens())
    assert set(shapes) == {"params"}
    assert weights.shapes(reference.param_spec(CONFIG)) \
        == weights.shapes(shapes["params"])
    layer = shapes["params"]["periods"]["layer_0"]
    assert "attn" not in layer and "mlp" in layer
    assert layer["mamba"]["in_proj"]["kernel"].shape \
        == (1, 32, 2 * 64 + 2 * 8 + 4)


@pytest.fixture(scope="module")
def followed():
    """The reference's first gradient and two steps, once for the
    module."""
    key, tokens = weights.seed_key(7), _tokens()
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.batch_loss(CONFIG, EINSUM, p, tokens)))(
            weights.make(key, reference.param_spec(CONFIG)))
    return want_loss, want_grads, reference.follow(CONFIG, WORKLOAD, key,
                                                   tokens, 2)


@pytest.mark.parametrize("remat", [True, False, "dots"])
def test_model_trains_through_the_compiled_step_as_the_reference(
        hvd_shutdown, followed, remat):
    """Two AdamW steps' losses and the first gradient (read back from
    AdamW's first moment) leaf by leaf, through
    ``make_compiled_train_step`` against the reference in float32 on
    seeded weights; with the layers rematerialised (``full``, which for
    a model with mamba layers keeps each scan's output by name, and
    ``dots``) and without; and the sums the step kept on the device.
    Tolerances: float32 sums in another order."""
    key, tokens = weights.seed_key(7), _tokens()
    spec = reference.param_spec(CONFIG)
    want_loss, want_grads, found = followed
    assert found["losses"][0] == pytest.approx(float(want_loss), abs=1e-6)

    hvd.init()
    policy = {"remat_policy": remat} if isinstance(remat, str) else {}
    loss_fn = make_fused_lm_loss(TransformerLM(_program_config(
        remat=bool(remat), **policy)), n_chunks=4)
    assert loss_fn.device_sums == SSM_DEVICE_SUMS
    step = hvd.make_compiled_train_step(
        loss_fn, optax.adamw(1e-3, weight_decay=1e-4))
    state = step.init_state(weights.make(key, spec))
    before = [telemetry.counter_total(n) for n in SSM_DEVICE_SUMS]
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
    # 2 steps x 64 tokens x 3 mamba layers, in chunks of 8
    delta = [telemetry.counter_total(n) - b
             for n, b in zip(SSM_DEVICE_SUMS, before)]
    assert delta == [2 * 64 * 3, 2 * 8 * 3, 0]


def test_full_remat_keeps_each_scans_output_and_nothing_else(capsys):
    """Under ``full`` a model with mamba layers keeps, beside every
    layer's input, each scan's output (d_inner wide, by its name) and
    no other width of a layer; a model without mamba layers keeps the
    layers' inputs alone."""
    tokens = _tokens()

    def kept_widths(**changes):
        model = TransformerLM(_program_config(remat=True, **changes))
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        jax.ad_checkpoint.print_saved_residuals(
            lambda p: model.apply({"params": p}, tokens).sum(), params)
        return [int(line.split("]")[0].split(",")[-1])
                for line in capsys.readouterr().out.splitlines()
                if "output of scan" in line]

    d, inner = CONFIG["hidden_size"], 64
    mamba = CONFIG["layer_types"].count("mamba")
    assert sorted(kept_widths()) == [d] * 5 + [inner] * mamba
    assert kept_widths(layer_types=("full_attention",) * 4) == [d] * 2


def test_an_unknown_kind_of_layer_is_refused_by_name():
    cfg = _program_config(layer_types=("mamba", "rwkv", "mamba", "mamba"))
    with pytest.raises(ValueError, match="rwkv"):
        TransformerLM(cfg).init(jax.random.PRNGKey(0), _tokens())
    with pytest.raises(ValueError, match="mamba_n_groups"):
        TransformerLM(_program_config(mamba_n_groups=3)).init(
            jax.random.PRNGKey(0), _tokens())
    with pytest.raises(ValueError, match="remat_policy"):
        TransformerLM(_program_config(remat=True,
                                      remat_policy="ssd_out")).init(
            jax.random.PRNGKey(0), _tokens())


# ---------------------------------------------------------------------------
# the four multipliers

PLAIN = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=1,
             head_dim=16, d_ff=48, max_seq_len=32, dtype=jnp.float32)


@pytest.mark.parametrize("layered", [False, True])
def test_multipliers_at_their_defaults_emit_nothing(layered):
    """At the defaults the program holds none of the multipliers'
    operations (the cells' StableHLO stays byte-equal to the parent's:
    ``tools/described_step.py`` from both trees), so an existing model's
    output is what it was; multipliers that are 1 in value (heads of 16:
    a score scale of 1/4 spelled out) emit their multiplies and give the
    same logits and fused loss to rounding."""
    kinds = dict(layer_types=("full_attention", "sliding_attention"),
                 sliding_window=8) if layered else {}
    spelled = dict(embedding_multiplier=1.0 + 1e-9, logits_scaling=1.0 + 1e-9,
                   residual_multiplier=1.0 + 1e-9, attention_multiplier=0.25)
    tokens = _tokens()
    model = TransformerLM(TransformerConfig(**PLAIN, **kinds))
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    other = TransformerLM(TransformerConfig(**PLAIN, **kinds, **spelled))
    _close(model.apply({"params": params}, tokens),
           other.apply({"params": params}, tokens), 1e-6)
    a, b = (jax.value_and_grad(make_fused_lm_loss(m, n_chunks=4))(
        params, tokens) for m in (model, other))
    jax.tree.map(lambda x, y: _close(x, y, 1e-6), a, b)

    def multiplies(m):
        return jax.jit(jax.grad(make_fused_lm_loss(m, n_chunks=4))).lower(
            params, tokens).as_text().count("stablehlo.multiply")

    # embedding, two branches and a score scale a layer, the logits
    # and the head's gradients: each at least once forward and backward
    assert multiplies(other) >= multiplies(model) + 2 * (1 + 3 + 1)


def test_each_multiplier_does_what_its_name_says():
    tokens = _tokens()
    base = TransformerConfig(**PLAIN)
    params = TransformerLM(base).init(jax.random.PRNGKey(0),
                                      tokens)["params"]

    def logits(**changes):
        return TransformerLM(dataclasses.replace(base, **changes)).apply(
            {"params": params}, tokens)

    _close(logits(logits_scaling=8.0), logits() / 8.0, 1e-6)
    scaled = jax.tree.map(lambda a: a, params)
    scaled["embed"] = params["embed"] * 12.0
    # a tied head: the embedding's rows times 12 are the head's too
    _close(logits(embedding_multiplier=12.0) * 12.0,
           TransformerLM(base).apply({"params": scaled}, tokens), 1e-4)
    assert float(jnp.abs(logits(residual_multiplier=0.22)
                         - logits()).max()) > 1e-3
    assert float(jnp.abs(logits(attention_multiplier=1 / 16)
                         - logits()).max()) > 1e-5


def test_logits_scaling_inside_the_chunked_loss_rule():
    """Value and both gradients of the fused loss with its logits
    divided by 8 against the plain loss of the divided logits."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (2, 16, 8), jnp.float32)
    emb = jax.random.normal(ks[1], (32, 8), jnp.float32)
    targets = jax.random.randint(ks[2], (2, 16), 0, 32)
    got = jax.value_and_grad(lambda x, e: chunked_lm_loss(
        x, e, targets, n_chunks=4, logits_scaling=8.0), argnums=(0, 1))(
            x, emb)
    want = jax.value_and_grad(lambda x, e: lm_loss(
        jnp.einsum("bsm,vm->bsv", x, e) / 8.0, targets), argnums=(0, 1))(
            x, emb)
    jax.tree.map(lambda a, b: _close(a, b, 1e-6), got, want)


# ---------------------------------------------------------------------------
# heads of 64 with a score scale of 1/64 through the flash kernels

@pytest.mark.parametrize("heads, kv", [(4, 1), (8, 2)])
def test_heads_of_64_scaled_by_a_64th_through_the_flash_kernels(heads, kv):
    """Granite's attention as the model hands it over: q times
    ``attention_multiplier * sqrt(D)`` = 0.125 ahead of kernels that
    scale by 1 / sqrt(D), keys and values repeated to the query heads;
    interpret mode against explicit scores ``q k^T / 64``, value and
    gradients."""
    B, S, D = 1, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = jax.random.normal(ks[0], (B, S, heads, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, kv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, kv, D), jnp.float32)
    ct = jax.random.normal(ks[3], (B, S, heads, D), jnp.float32)

    def model_way(q, k, v):
        out = flash_attention(
            q * (1 / 64 * np.sqrt(D)), jnp.repeat(k, heads // kv, axis=2),
            jnp.repeat(v, heads // kv, axis=2), interpret=True)
        return jnp.sum(out * ct), out

    def explicit(q, k, v):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q,
                            jnp.repeat(k, heads // kv, axis=2)) / 64.0
        mask = jnp.tril(jnp.ones((S, S), bool))
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs,
                         jnp.repeat(v, heads // kv, axis=2))
        return jnp.sum(out * ct), out

    (_, out), grads = jax.value_and_grad(
        model_way, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, want), want_grads = jax.value_and_grad(
        explicit, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    _close(out, want, 2e-3)
    for g, w in zip(grads, want_grads):
        _close(g, w, 5e-3)
