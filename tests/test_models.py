"""Model zoo smoke + correctness tests (single device)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (
    ResNet50, TransformerConfig, TransformerLM, chunked_lm_loss,
    lm_loss,
)
from horovod_tpu.models.resnet import ResNet
from horovod_tpu.models.transformer import dense_causal_attention


def test_resnet_forward_shapes():
    model = ResNet(stage_sizes=[1, 1, 1, 1], num_classes=10,
                   num_filters=8, dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32


def test_resnet_train_mode_updates_batch_stats():
    model = ResNet(stage_sizes=[1, 1, 1, 1], num_classes=4,
                   num_filters=8, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out, mutated = model.apply(variables, x, train=True,
                               mutable=["batch_stats"])
    assert out.shape == (2, 4)
    before = jax.tree_util.tree_leaves(variables["batch_stats"])
    after = jax.tree_util.tree_leaves(mutated["batch_stats"])
    assert any(not np.allclose(b, a) for b, a in zip(before, after))


def test_resnet50_param_count():
    """torchvision's resnet50 (the reference benchmark's model,
    examples/pytorch/pytorch_synthetic_benchmark.py) leaf for leaf:
    the tree the benchmark's control cell trains."""
    model = ResNet50(num_classes=1000)
    x = jnp.zeros((1, 224, 224, 3))
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x, train=False))
    params = jax.tree_util.tree_leaves_with_path(variables["params"])
    assert sum(int(np.prod(p.shape)) for _, p in params) == 25_557_032
    names = [path[-1].key for path, _ in params]
    # 53 convolutions and the head; a scale and a bias per BatchNorm
    assert len(params) == 161
    assert names.count("kernel") == 54
    assert names.count("scale") == 53 and names.count("bias") == 54
    assert len(jax.tree_util.tree_leaves(variables["batch_stats"])) == 106


def test_vgg16_param_count_and_forward():
    # ~138.4M params, matching the canonical VGG-16 of the reference's
    # benchmark trio (docs/benchmarks.rst:13-14, 68% scaling case).
    from horovod_tpu.models import VGG16

    model = VGG16(num_classes=1000, dtype=jnp.float32)
    x = jnp.zeros((1, 224, 224, 3))
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x, train=False))
    n = sum(int(np.prod(p.shape))
            for p in jax.tree_util.tree_leaves(variables["params"]))
    assert 138.0e6 < n < 138.8e6, n
    small = VGG16(num_classes=10, dtype=jnp.float32)
    xs = jnp.zeros((2, 64, 64, 3))
    vs = small.init(jax.random.PRNGKey(0), xs, train=False)
    out = small.apply(vs, xs, train=False)
    assert out.shape == (2, 10)


def test_inception_v3_param_count_and_forward():
    # ~23.8M params (no aux head), matching canonical Inception V3
    # (docs/benchmarks.rst:13, 90% scaling case).
    from horovod_tpu.models import InceptionV3

    model = InceptionV3(num_classes=1000, dtype=jnp.float32)
    x = jnp.zeros((1, 299, 299, 3))
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x, train=False))
    n = sum(int(np.prod(p.shape))
            for p in jax.tree_util.tree_leaves(variables["params"]))
    assert 23.5e6 < n < 24.2e6, n
    small = InceptionV3(num_classes=10, dtype=jnp.float32)
    xs = jnp.zeros((2, 96, 96, 3))
    vs = small.init(jax.random.PRNGKey(0), xs, train=False)
    out, mutated = small.apply(vs, xs, train=True,
                               mutable=["batch_stats"])
    assert out.shape == (2, 10)
    assert "batch_stats" in mutated


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = TransformerConfig(vocab_size=128, d_model=64, n_layers=2,
                            n_heads=4, d_ff=128, max_seq_len=64,
                            dtype=jnp.float32)
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 128)
    params = model.init(jax.random.PRNGKey(1), tokens)
    return cfg, model, params, tokens


def test_transformer_forward(tiny_lm):
    cfg, model, params, tokens = tiny_lm
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 16, 128)
    loss = lm_loss(logits, tokens)
    assert np.isfinite(float(loss))


def test_chunked_lm_loss_matches_unfused(tiny_lm):
    """chunked_lm_loss (logits projection fused into the loss, never
    materializing (B, S, V)) equals lm_loss in value AND gradients —
    both the pre-shifted form and the rolled-targets + weights form
    the MFU bench uses."""
    cfg, model, params, tokens = tiny_lm

    def unfused(p):
        logits = model.apply({"params": p["params"]}, tokens)
        return lm_loss(logits[:, :-1], tokens[:, 1:])

    def fused_shifted(p):
        x, emb = model.apply({"params": p["params"]}, tokens,
                             pre_logits=True)
        return chunked_lm_loss(x[:, :-1], emb, tokens[:, 1:],
                               n_chunks=5)          # S-1 = 15 = 5*3

    def fused_weighted(p):
        x, emb = model.apply({"params": p["params"]}, tokens,
                             pre_logits=True)
        targets = jnp.roll(tokens, -1, axis=1)
        w = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
        return chunked_lm_loss(x, emb, targets, n_chunks=4, weights=w)

    la, ga = jax.value_and_grad(unfused)(params)
    for fused in (fused_shifted, fused_weighted):
        lb, gb = jax.value_and_grad(fused)(params)
        assert abs(float(la) - float(lb)) < 1e-5
        for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
            np.testing.assert_allclose(a, b, atol=1e-5)

    with pytest.raises(ValueError, match="not divisible"):
        x, emb = model.apply(params, tokens, pre_logits=True)
        chunked_lm_loss(x, emb, tokens, n_chunks=7)


_HEAD_V, _HEAD_M, _HEAD_S = 24, 8, 12      # vocabulary unlike any other


def _unfused_ce(x, head, targets, w):
    """The weighted mean cross-entropy with every logit held, float32."""
    logp = jax.nn.log_softmax(jnp.einsum("bsm,vm->bsv", x, head))
    nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    if w is None:
        return jnp.mean(nll)
    total = jnp.sum(w)
    return jnp.sum(nll * w) / jnp.where(total > 0, total, 1.0)


def _head_problem(tied, weights):
    """A head's whole setting in a dozen lines: ``(fused, unfused,
    params, tokens)``, the two losses of one small model whose head is
    the embedding (``tied``) or a matrix of its own, with per-token
    weights that are absent, constant, or a function of another
    parameter (the looped model's ``p * w``)."""
    keys = jax.random.split(jax.random.PRNGKey(3), 5)

    def normal(key, *shape):
        return jax.random.normal(key, shape, jnp.float32) * 0.5

    params = {"embed": normal(keys[0], _HEAD_V, _HEAD_M),
              "mix": normal(keys[1], _HEAD_M, _HEAD_M),
              "gate": normal(keys[2], _HEAD_M)}
    if not tied:
        params["lm_head"] = normal(keys[3], _HEAD_V, _HEAD_M)
    tokens = jax.random.randint(keys[4], (2, _HEAD_S), 0, _HEAD_V)

    def parts(p, tokens):
        x = jnp.tanh(p["embed"][tokens] @ p["mix"])
        w = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
        if weights == "none":
            w = None
        elif weights == "differentiable":
            w = jax.nn.sigmoid(x @ p["gate"]) * w
        return (x, p["embed"] if tied else p["lm_head"],
                jnp.roll(tokens, -1, axis=1), w)

    def fused(p, tokens):
        x, head, targets, w = parts(p, tokens)
        return chunked_lm_loss(x, head, targets, n_chunks=3, weights=w)

    def unfused(p, tokens):
        return _unfused_ce(*parts(p, tokens))

    return fused, unfused, params, tokens


@pytest.mark.parametrize("ranks", [None, 2], ids=["plain", "vmap"])
@pytest.mark.parametrize("weights",
                         ["none", "constant", "differentiable"])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_chunked_lm_loss_gradient_formed_in_forward(tied, weights, ranks):
    """The chunked loss's own rule (its gradient is formed in the scan
    that forms the logits) against plain autodiff of the unfused
    float32 loss: value and every parameter's gradient, the weights'
    own included, for both heads, and under ``jax.vmap`` over a
    leading rank axis as the one-rank step body runs it."""
    fused, unfused, params, tokens = _head_problem(tied, weights)
    grad = jax.value_and_grad
    if ranks:
        params = jax.tree.map(
            lambda a: jnp.stack([a * (1 + 0.1 * r) for r in range(ranks)]),
            params)
        tokens = jnp.stack([jnp.roll(tokens, r) for r in range(ranks)])
        grad = lambda f: jax.vmap(jax.value_and_grad(f))    # noqa: E731
    la, ga = grad(unfused)(params, tokens)
    lb, gb = grad(fused)(params, tokens)
    np.testing.assert_allclose(la, lb, atol=1e-6)
    assert set(ga) == set(gb)
    for name in ga:
        np.testing.assert_allclose(ga[name], gb[name], atol=1e-6,
                                   err_msg=name)
    gate = np.abs(np.asarray(gb["gate"])).max()
    assert (gate > 1e-4) == (weights == "differentiable")


def test_chunked_lm_loss_weights_cotangent_and_zero_sum():
    """The weights as an argument: their gradient is each token's own
    ``(lse - tgt)`` over the sum, less the loss over the sum; and a
    weight sum of zero gives loss 0 and zero, finite gradients."""
    _, _, params, tokens = _head_problem(True, "constant")
    x = params["embed"][tokens]
    targets = jnp.roll(tokens, -1, axis=1)
    w = jnp.linspace(0.0, 1.0, tokens.size, dtype=jnp.float32).reshape(
        tokens.shape)

    def unfused(x, head, w):
        return _unfused_ce(x, head, targets, w)

    def loss(x, head, w):
        return chunked_lm_loss(x, head, targets, n_chunks=4, weights=w)

    for weights in (w, jnp.zeros_like(w)):
        la, ga = jax.value_and_grad(unfused, (0, 1, 2))(
            x, params["embed"], weights)
        lb, gb = jax.value_and_grad(loss, (0, 1, 2))(
            x, params["embed"], weights)
        np.testing.assert_allclose(la, lb, atol=1e-6)
        for a, b in zip(ga, gb):
            assert np.isfinite(b).all()
            np.testing.assert_allclose(a, b, atol=1e-6)
    assert float(lb) == 0.0
    assert not np.asarray(gb[0]).any() and not np.asarray(gb[1]).any()


def _equations(jaxpr, in_scan=False):
    """Every equation of a jaxpr and of the jaxprs inside it, each with
    whether a ``scan`` body holds it."""
    for eqn in jaxpr.eqns:
        yield eqn, in_scan
        inner = in_scan or eqn.primitive.name == "scan"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, inner)


def test_chunked_lm_loss_projects_each_chunk_once():
    """What a CPU can decide of the program: the gradient of the
    chunked loss holds no ``checkpoint`` and, in its scan bodies,
    exactly the three products of the vocabulary's width that the
    mathematics needs (logits, dx, d head), where recomputing the
    logits in the backward made four."""
    fused, _, params, tokens = _head_problem(False, "differentiable")
    jaxpr = jax.make_jaxpr(jax.grad(fused))(params, tokens).jaxpr
    equations = list(_equations(jaxpr))
    names = {eqn.primitive.name for eqn, _ in equations}
    assert "scan" in names and "custom_vjp_call" not in names
    assert not {n for n in names if "remat" in n or "checkpoint" in n}
    wide = [eqn for eqn, in_scan in equations
            if in_scan and eqn.primitive.name == "dot_general"
            and any(_HEAD_V in v.aval.shape
                    for v in eqn.invars + eqn.outvars)]
    assert len(wide) == 3
    # forward-mode differentiation is the price (the docstring says so)
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(lambda p: fused(p, tokens), (params,), (params,))


def _flash_lm(flash=True, **changes):
    """A three-layer model (ONE scan's body) on the flash inner, the
    interpreter's here, or on the dense reference inner."""
    from horovod_tpu.ops.pallas_kernels import flash_attention

    cfg = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=3, n_heads=2, d_ff=64,
        max_seq_len=32, dtype=jnp.float32, **changes)
    model = TransformerLM(cfg, attention_fn=flash_attention if flash
                          else dense_causal_attention)
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 128)
    return model, model.init(jax.random.PRNGKey(1), toks)["params"], toks


def _remat(policy):
    return {"remat": False} if policy is None \
        else {"remat": True, "remat_policy": policy}


REMAT_POLICIES = ["full", "dots", "dots_flash", None]


@pytest.mark.parametrize("policy", REMAT_POLICIES)
def test_every_remat_policy_gives_the_flash_inners_loss_and_gradients(
        policy):
    """Every policy keeps the flash kernels' outputs (out + lse,
    checkpoint-named) and the backward pass reads the kept values where
    a replay would compute them again: the loss and gradients of the
    flash inner without remat.  Without remat: those of the dense
    reference inner."""
    from horovod_tpu.models import make_fused_lm_loss

    def run(flash, **changes):
        model, params, toks = _flash_lm(flash, **changes)
        return jax.jit(jax.value_and_grad(
            make_fused_lm_loss(model, 4)))(params, toks)

    got = run(True, **_remat(policy))
    want = run(policy is not None, remat=False)
    assert abs(float(got[0]) - float(want[0])) < 1e-6
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_an_unknown_remat_policy_is_refused():
    with pytest.raises(ValueError, match="remat_policy"):
        cfg = TransformerConfig(
            vocab_size=128, d_model=32, n_layers=2, n_heads=2, d_ff=64,
            max_seq_len=32, remat=True, remat_policy="bogus")
        TransformerLM(cfg).init(jax.random.PRNGKey(1),
                                jnp.zeros((2, 32), jnp.int32))


def _flash_calls(**changes):
    """``(flash_fwd, flash_dkv)`` calls in the gradient of the model's
    jaxpr: the forward's scan body and the backward's, each once."""
    model, params, toks = _flash_lm(**changes)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: model.apply({"params": p}, toks).sum()))(params))
    return tuple(len(re.findall(rf"name={name}\b", text))
                 for name in ("flash_fwd", "flash_dkv"))


@pytest.mark.parametrize("policy", REMAT_POLICIES)
def test_a_remat_replay_runs_no_flash_kernel(policy):
    """One rule for the Pallas kernels (the scan's:
    ``test_ssd_kernels.py::test_a_remat_replay_runs_no_scan``): every
    policy keeps the forward kernel's two outputs by name, so the
    gradient holds one ``flash_fwd`` and one ``flash_dkv`` a layer body
    and no second forward, as without remat.  With the names NOT kept
    the replay runs the forward again (the next test: the count can
    tell)."""
    assert _flash_calls(**_remat(policy)) == (1, 1)


@pytest.mark.parametrize("policy", ["full", "dots"])
def test_a_replay_without_the_kept_outputs_runs_the_flash_forward_again(
        monkeypatch, policy):
    from horovod_tpu.models import transformer

    monkeypatch.setattr(transformer, "FLASH_KEPT", ())
    assert _flash_calls(remat=True, remat_policy=policy) == (2, 1)


def test_full_remat_keeps_a_layers_input_and_the_flash_outputs_alone(
        capsys):
    """What ``full`` holds of a layer application: its input, the
    forward kernel's output of the same size (heads x head_dim wide)
    and the row sums' logarithms (a float32 a head and position), each
    stacked over the three layers, and no other value of a layer."""
    model, params, toks = _flash_lm(remat=True)
    jax.ad_checkpoint.print_saved_residuals(
        lambda p: model.apply({"params": p}, toks).sum(), params)
    kept = sorted(
        line.split(" ")[0] for line in capsys.readouterr().out.splitlines()
        if "from the argument" not in line and line.startswith("f32[3,"))
    # (layers, B, S, d_model); (layers, B x heads, S, head_dim);
    # (layers, B x heads, 1, S)
    assert kept == ["f32[3,2,32,32]", "f32[3,4,1,32]", "f32[3,4,32,16]"]


def test_transformer_scan_layer_axis(tiny_lm):
    cfg, model, params, tokens = tiny_lm
    # nn.scan stacks per-layer params along a leading axis of length
    # n_layers — the pipeline-parallel stage axis.
    wq = params["params"]["layers"]["attn"]["wq"]["kernel"]
    assert wq.shape[0] == cfg.n_layers


def test_transformer_causality(tiny_lm):
    cfg, model, params, tokens = tiny_lm
    logits1 = model.apply(params, tokens)
    perturbed = tokens.at[:, -1].set((tokens[:, -1] + 1) % 128)
    logits2 = model.apply(params, perturbed)
    # changing the last token must not affect logits at earlier positions
    np.testing.assert_allclose(np.asarray(logits1[:, :-1]),
                               np.asarray(logits2[:, :-1]),
                               rtol=1e-5, atol=1e-5)


def test_moe_forward():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=2, d_ff=64, max_seq_len=32,
                            num_experts=4, expert_top_k=2,
                            dtype=jnp.float32)
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 8), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 8, 64)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_attention_offset_matches_full():
    # Sharded-sequence contract: attention over the full K/V with query
    # offset o equals rows [o:o+s) of full attention.
    B, S, H, D = 1, 16, 2, 8
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(kk, (B, S, H, D))
               for kk in jax.random.split(key, 3))
    full = dense_causal_attention(q, k, v)
    half = dense_causal_attention(q[:, 8:], k, v, offset=8)
    np.testing.assert_allclose(np.asarray(full[:, 8:]), np.asarray(half),
                               rtol=1e-5, atol=1e-5)


def test_vit_b16_param_count_and_forward():
    from horovod_tpu.models import ViT_B16
    model = ViT_B16(num_classes=1000)
    x = jnp.zeros((1, 224, 224, 3), jnp.float32)
    variables = jax.jit(lambda: model.init(jax.random.PRNGKey(0), x))()
    n = sum(int(np.prod(p.shape)) for p in
            jax.tree_util.tree_leaves(variables["params"]))
    # canonical ViT-B/16: 86.6M params
    assert 85e6 < n < 88e6, n
    out = model.apply(variables, x)
    assert out.shape == (1, 1000)
    assert out.dtype == jnp.float32


def test_vit_small_trains():
    from horovod_tpu.models import ViT, ViTConfig
    import optax
    cfg = ViTConfig(image_size=32, patch_size=8, d_model=64, n_layers=2,
                    n_heads=2, d_ff=128, num_classes=10,
                    dtype=jnp.float32)
    model = ViT(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 32, 3))
    y = jax.random.randint(jax.random.PRNGKey(1), (4,), 0, 10)
    params = model.init(jax.random.PRNGKey(2), x)["params"]
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            logits = model.apply({"params": p}, x, train=True)
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    first = None
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state)
        if first is None:
            first = float(loss)
    assert float(loss) < first, (first, float(loss))


def test_kv_cache_decode_matches_full_forward():
    """Greedy decoding with the KV cache must produce exactly the
    tokens the full re-forward would pick at every position."""
    from horovod_tpu.models import make_generate_fn
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=2, d_ff=64, max_seq_len=32,
                            dtype=jnp.float32)
    model = TransformerLM(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(0), (2, 5), 0, 64)
    params = model.init(jax.random.PRNGKey(1), prompt)["params"]

    gen = make_generate_fn(model, max_new_tokens=6)
    cached = np.asarray(gen(params, prompt))

    # reference: re-run the full forward each step, argmax the last
    toks = prompt
    expected = []
    for _ in range(6):
        logits = model.apply({"params": params}, toks)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        expected.append(nxt)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    expected = np.stack([np.asarray(e) for e in expected], axis=1)
    assert np.array_equal(cached, expected), (cached, expected)


def test_gqa_forward_trains_and_caches():
    """Grouped-query attention (n_kv_heads < n_heads, llama style):
    forward shapes hold, causality holds, the model trains, the KV
    cache stores the REDUCED head count, and cached greedy decoding
    matches the full re-forward exactly."""
    from horovod_tpu.models import make_generate_fn
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=4, n_kv_heads=2, d_ff=64,
                            max_seq_len=32, dtype=jnp.float32)
    model = TransformerLM(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(0), (2, 5), 0, 64)
    params = model.init(jax.random.PRNGKey(1), prompt)["params"]

    # kv projections carry the reduced head count
    wk = params["layers"]["attn"]["wk"]["kernel"]
    wq = params["layers"]["attn"]["wq"]["kernel"]
    assert wk.shape[-2] == 2 and wq.shape[-2] == 4, (wk.shape, wq.shape)

    logits = model.apply({"params": params}, prompt)
    assert logits.shape == (2, 5, 64)

    # causality: future-token perturbation cannot change earlier rows
    prompt2 = prompt.at[:, -1].set((prompt[:, -1] + 1) % 64)
    logits2 = model.apply({"params": params}, prompt2)
    np.testing.assert_allclose(np.asarray(logits[:, :-1]),
                               np.asarray(logits2[:, :-1]),
                               rtol=1e-5, atol=1e-5)

    # cache stores KV heads (half of H) and cached decode is exact
    gen = make_generate_fn(model, max_new_tokens=4)
    cached = np.asarray(gen(params, prompt))
    _, vars_ = model.apply({"params": params}, prompt, decode=True,
                           mutable=["cache"])
    k_cache = jax.tree_util.tree_leaves(
        {"k": vars_["cache"]["layers"]["attn"]["k"]})[0]
    assert k_cache.shape[-2] == 2, k_cache.shape

    toks = prompt
    expected = []
    for _ in range(4):
        lg = model.apply({"params": params}, toks)
        nxt = jnp.argmax(lg[:, -1], axis=-1)
        expected.append(nxt)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    expected = np.stack([np.asarray(e) for e in expected], axis=1)
    assert np.array_equal(cached, expected), (cached, expected)

    # invalid head grouping fails loudly
    with pytest.raises(ValueError, match="n_kv_heads"):
        TransformerConfig(vocab_size=64, d_model=32, n_layers=1,
                          n_heads=4, n_kv_heads=3, d_ff=64,
                          max_seq_len=8).kv_heads


def test_attention_window_consistent_train_and_decode():
    """TransformerConfig(attention_window=W): the dense and flash
    training paths compute the same windowed logits, cached greedy
    decode matches the windowed full re-forward exactly, and the
    sequence-parallel inners reject the window loudly instead of
    silently training full-causal."""
    from functools import partial

    from horovod_tpu.models import make_generate_fn
    from horovod_tpu.ops.pallas_kernels import flash_attention

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=2, d_ff=64, max_seq_len=32,
                            attention_window=8, dtype=jnp.float32)
    model = TransformerLM(cfg)                      # dense windowed
    prompt = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 64)
    params = model.init(jax.random.PRNGKey(1), prompt)["params"]
    logits_dense = model.apply({"params": params}, prompt)

    # flash inner gets the same window from the config
    flash_model = TransformerLM(cfg, attention_fn=partial(
        flash_attention, block_q=8, block_k=8, interpret=True))
    logits_flash = flash_model.apply({"params": params}, prompt)
    np.testing.assert_allclose(np.asarray(logits_dense),
                               np.asarray(logits_flash),
                               rtol=2e-4, atol=2e-4)

    # the window actually binds: full-causal logits differ
    logits_full = TransformerLM(
        TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                          n_heads=2, d_ff=64, max_seq_len=32,
                          dtype=jnp.float32)).apply(
        {"params": params}, prompt)
    assert not np.allclose(np.asarray(logits_dense),
                           np.asarray(logits_full), atol=1e-3)

    # cached decode applies the SAME window as training
    gen = make_generate_fn(model, max_new_tokens=4)
    short = prompt[:, :20]
    cached = np.asarray(gen(params, short))
    toks = short
    expected = []
    for _ in range(4):
        lg = model.apply({"params": params}, toks)
        nxt = jnp.argmax(lg[:, -1], axis=-1)
        expected.append(nxt)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    expected = np.stack([np.asarray(e) for e in expected], axis=1)
    assert np.array_equal(cached, expected), (cached, expected)

    # inners without window support fail loudly
    def no_window_attn(q, k, v):
        return q

    bad = TransformerLM(cfg, attention_fn=no_window_attn)
    with pytest.raises(ValueError, match="window"):
        bad.init(jax.random.PRNGKey(2), prompt)


def test_kv_cache_decode_sampling_reproducible():
    from horovod_tpu.models import make_generate_fn
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=2, d_ff=64, max_seq_len=16,
                            dtype=jnp.float32)
    model = TransformerLM(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(0), (1, 3), 0, 64)
    params = model.init(jax.random.PRNGKey(1), prompt)["params"]
    gen = make_generate_fn(model, max_new_tokens=4, temperature=0.8)
    a = np.asarray(gen(params, prompt, rng=jax.random.PRNGKey(7)))
    b = np.asarray(gen(params, prompt, rng=jax.random.PRNGKey(7)))
    assert np.array_equal(a, b)
    with pytest.raises(ValueError, match="rng"):
        gen(params, prompt)
    with pytest.raises(ValueError, match="max_seq_len"):
        make_generate_fn(model, max_new_tokens=20)(params, prompt)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_lm_under_a_reducing_step_is_the_same_model(remat):
    """While a data-parallel step traces (``grad_hook``'s context),
    ``TransformerLM`` routes each layer's parameters through the hook:
    ``init`` gives the same tree and values, the forward pass the same
    logits, the layers' cotangents pass the step's reduction exactly
    once and the embedding's not at all, and decoding with a KV cache
    still runs.  Outside a context the hook is never entered."""
    from horovod_tpu.models import make_generate_fn
    from horovod_tpu.ops import grad_hook

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_kv_heads=2, n_layers=3, d_ff=64,
                            max_seq_len=16, dtype=jnp.float32, remat=remat)
    model = TransformerLM(cfg)
    tokens = jnp.arange(16, dtype=jnp.int32)[None] % 64
    key = jax.random.PRNGKey(0)

    def grads(p):
        return jax.grad(lambda q: model.apply({"params": q},
                                              tokens).sum())(p)

    params = model.init(key, tokens)["params"]
    plain = model.apply({"params": params}, tokens), grads(params)
    generate = make_generate_fn(model, max_new_tokens=3)
    said = generate(params, tokens[:, :4])
    with grad_hook.reducing_in_backward(lambda g: 2 * g, "hook") as step:
        inside = model.init(key, tokens)["params"]
        assert not step.covered         # no hook while initializing
        hooked = model.apply({"params": params}, tokens), grads(params)
        assert step.covered == [("layers",)]
        said_inside = make_generate_fn(model, max_new_tokens=3)(
            params, tokens[:, :4])
    assert jax.tree.structure(inside) == jax.tree.structure(params)
    jax.tree.map(np.testing.assert_array_equal, inside, params)
    np.testing.assert_array_equal(hooked[0], plain[0])
    np.testing.assert_array_equal(said_inside, said)
    jax.tree.map(lambda h, p: np.testing.assert_allclose(
        h, 2 * p, rtol=1e-5, atol=1e-6),
        hooked[1]["layers"], plain[1]["layers"])
    for name in ("embed", "ln_final"):
        jax.tree.map(lambda h, p: np.testing.assert_allclose(
            h, p, rtol=1e-5, atol=1e-6), hooked[1][name], plain[1][name])
    assert not grad_hook.reduces_in_backward()
