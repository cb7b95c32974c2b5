"""Plain float32 reference of the ``afmoe_train`` adapter: Trinity-Mini's
layer as published (``transformers`` ``models/afmoe/modeling_afmoe.py``)
trained with AdamW on next-token cross-entropy, for ONE chip's share of
an expert-parallel deployment: the router scores all the published
experts, and of ``sum_j w_j SwiGLU_{idx_j}(m)`` only the terms of the
experts held here are computed; the vocabulary is the slice held here.

With ``norm`` = RMS norm (learned scale), per layer::

    a = norm_in(x);      x = x + norm_post_attn(Attn(a))
    m = norm_pre_mlp(x); x = x + norm_post_mlp(FF(m))
    Attn(a) = (softmax_causal(norm_q(a Wq) norm_k(a Wk)^T / sqrt(D)) a Wv
               * sigmoid(a Wg)) Wo
      rotary positions on q and k of a sliding layer only, which also
      sees only the last ``sliding_window`` positions
    FF, dense layers:  SwiGLU(m), width intermediate_size
    FF, expert layers: s = sigmoid(m Wr); idx = top_k(s + b); w =
      s[idx] / (sum s[idx] + 1e-20) * route_scale;
      SwiGLU_shared(m) + sum_{j: idx_j held} w_j SwiGLU_{idx_j}(m)

and ``x = E[tokens] * sqrt(d)`` before, ``norm_final`` and the untied
head after.  ``b`` (expert_bias) starts at zero and after every step
moves by ``load_balance_coeff`` toward the experts that got fewer of the
step's tokens than the mean: ``b += coeff * sign(mean(n) - n)``, with
``n`` the tokens each of the router's experts was chosen by.

Straightforward ``jax.numpy``: no kernel, no bfloat16, no fused loss, no
sorting of tokens (every held expert sees every token of a block, times
its weight or zero), nothing imported from the program.  Attention
takes the rows one after another, everything else blocks of tokens, so
that it fits, alone, on one chip at 2 x 8,192 tokens.  The pieces that
do not depend on the architecture (the norm, the rotary positions, the
masked attention in blocks, AdamW from the gradients' history) are the
``lm_train`` reference's.
"""

import math

import jax
import jax.numpy as jnp

from chipbench import weights
from chipbench.references import precision
from chipbench.references.lm_train import (
    _adamw_update, _attention, _rms_norm, _rope)
from chipbench.weights import Leaf


def first_loss(config):
    """Seeded weights know nothing, so the first loss is ln(vocab) and
    half the variance of the logits: a normalised hidden state (norm
    sqrt(d)) against rows of the head (std ``initializer_range``)."""
    return math.log(config["vocab_size"]) \
        + config["hidden_size"] * config["initializer_range"] ** 2 / 2


def _periods(config):
    """(kinds of the leading dense layers' one period, its repeats,
    kinds of the expert layers' period, its repeats), as the program
    stacks them: the shortest pattern whose repetition gives the
    layers' kinds."""
    kinds, lead = list(config["layer_types"]), config["num_dense_layers"]

    def shortest(group):
        n = len(group)
        for p in range(1, n + 1):
            if n % p == 0 and group == group[:p] * (n // p):
                return group[:p], n // p
        return [], 0

    return shortest(kinds[:lead]) + shortest(kinds[lead:])


def param_spec(config):
    d, hd = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    ff, width = config["intermediate_size"], config["moe_intermediate_size"]
    held, routed = config["num_experts"], config["published"]["num_experts"]
    shared = width * config["num_shared_experts"]
    dense_kinds, dense_n, expert_kinds, expert_n = _periods(config)

    def layer(n, feed_forward):
        def normal(fan_in, *shape):
            return Leaf((n,) + shape, "normal", 1.0 / math.sqrt(fan_in))

        def kernel(fan_in, *shape):
            return {"kernel": normal(fan_in, *shape)}

        def scale(width, kind="ones", std=1.0):
            return {"scale": Leaf((n, width), kind, std)}

        # the norms AFTER attention and feed-forward start small (drawn
        # about zero, rms 0.1; the configuration's ``assumed`` says why):
        # seeded attention averages thousands of values, so its output
        # is nearly the same for every token of a row, and a norm of
        # scale 1 would blow that shared part up to the size of the
        # token's own embedding; every token would then pick the same
        # experts, which no trained model's router sees
        after = ("normal", 0.1)

        def swiglu(f):
            return {"wi_gate": kernel(d, d, f), "wi_up": kernel(d, d, f),
                    "wo": kernel(f, f, d)}

        out = {
            "attn": {"wq": kernel(d, d, heads, hd),
                     "wk": kernel(d, d, kv, hd),
                     "wv": kernel(d, d, kv, hd),
                     "wg": kernel(d, d, heads, hd),
                     "wo": kernel(heads * hd, heads, hd, d),
                     "q_norm": scale(hd), "k_norm": scale(hd)},
            "ln_attn": scale(d), "ln_post_attn": scale(d, *after),
            "ln_mlp": scale(d), "ln_post_mlp": scale(d, *after),
        }
        if feed_forward == "dense":
            out["mlp"] = swiglu(ff)
        else:
            out["moe"] = {"router": normal(d, d, routed),
                          "wi_gate": normal(d, held, d, width),
                          "wi_up": normal(d, held, d, width),
                          "wo": normal(width, held, width, d),
                          "shared": swiglu(shared)}
        return out

    spec = {
        "embed": Leaf((config["vocab_size"], d), "normal",
                      config["initializer_range"]),
        "lm_head": Leaf((config["vocab_size"], d), "normal",
                        config["initializer_range"]),
        "ln_final": {"scale": Leaf((d,), "ones")},
    }
    if dense_n:
        spec["dense_layers"] = {f"layer_{i}": layer(dense_n, "dense")
                                for i in range(len(dense_kinds))}
    if expert_n:
        spec["periods"] = {f"layer_{i}": layer(expert_n, "experts")
                           for i in range(len(expert_kinds))}
    return spec


def aux_spec(config):
    """What the training loop keeps beside the parameters: every expert
    layer's expert_bias, as the program's model names it."""
    _, _, expert_kinds, expert_n = _periods(config)
    routed = config["published"]["num_experts"]
    return {"router_state": {"periods": {
        f"layer_{i}": {"moe": {"expert_bias": Leaf((expert_n, routed),
                                                   "zeros")}}
        for i in range(len(expert_kinds))}}}


def updated_bias(config, aux, counts):
    """``aux`` (``aux_spec``'s tree) after a step whose expert layers'
    experts were chosen by ``counts`` ({layer: (repeats, experts)})
    tokens."""
    def one(bias, n):
        n = n.astype(jnp.float32)
        return bias + config["load_balance_coeff"] * jnp.sign(
            jnp.mean(n, axis=-1, keepdims=True) - n)

    return {"router_state": {"periods": {
        layer: {"moe": {"expert_bias": one(
            state["moe"]["expert_bias"], counts[layer])}}
        for layer, state in aux["router_state"]["periods"].items()}}}


def _swiglu(einsum, x, p):
    gate = jax.nn.silu(einsum("sd,df->sf", x, p["wi_gate"]["kernel"]))
    up = einsum("sd,df->sf", x, p["wi_up"]["kernel"])
    return einsum("sf,fd->sd", gate * up, p["wo"]["kernel"])


def routing(config, einsum, m, router, bias):
    """(weights, idx), both (T, experts per token), over ALL the
    experts the router scores."""
    scores = jax.nn.sigmoid(einsum("sd,de->se", m, router))
    _, idx = jax.lax.top_k(scores + bias, config["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return picked * config["route_scale"], idx


def routed_experts(config, einsum, m, p, bias, first_expert):
    """The held experts' part of ``sum_j w_j SwiGLU_{idx_j}(m)``: each
    held expert on every token of ``m``, times the weight the router
    gave it for that token, which is zero where it was not chosen; and
    how many tokens chose each of the router's experts."""
    w, idx = routing(config, einsum, m, p["router"], bias)
    counts = jnp.sum(idx[:, :, None] == jnp.arange(p["router"].shape[-1]),
                     axis=(0, 1))
    held = p["wi_gate"].shape[0]
    chosen = idx[:, :, None] == first_expert + jnp.arange(held)[None, None]
    weight = jnp.sum(jnp.where(chosen, w[:, :, None], 0.0), axis=1)

    def one_expert(total, expert):
        gate, up, down, w_e = expert
        hidden = jax.nn.silu(einsum("sd,df->sf", m, gate)) \
            * einsum("sd,df->sf", m, up)
        return total + einsum("sf,fd->sd", hidden, down) * w_e[:, None], None

    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (p["wi_gate"], p["wi_up"], p["wo"], weight.T))
    return total, counts


def batch_loss(config, einsum, params, batch, aux=None):
    """Mean next-token cross-entropy of a batch of token rows (R, S),
    and, with ``aux`` (``aux_spec``'s tree: the expert_bias of every
    expert layer; zero without it), ``(loss, {layer: tokens by expert
    (repeats, experts)})``."""
    routed = config["published"]["num_experts"]
    if config["score_func"] != "sigmoid" or not config["route_norm"]:
        raise NotImplementedError("a router that is not a sigmoid with "
                                  "renormalised weights")
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    d, seq = config["hidden_size"], batch.shape[1]
    block = math.gcd(seq, 512)
    first_expert = config.get("deployment", {}).get("first_expert_held", 0)
    if config["tie_word_embeddings"]:
        raise NotImplementedError("a tied output head")

    def blocks(x):
        return x.reshape((-1, block) + x.shape[2:])

    def attention_row(x, p, kind):
        sliding = kind == "sliding_attention"
        a = _rms_norm(x, p["ln_attn"]["scale"], eps)
        q = einsum("sd,dhe->she", a, p["attn"]["wq"]["kernel"])
        k = einsum("sd,dhe->she", a, p["attn"]["wk"]["kernel"])
        v = einsum("sd,dhe->she", a, p["attn"]["wv"]["kernel"])
        g = einsum("sd,dhe->she", a, p["attn"]["wg"]["kernel"])
        q = _rms_norm(q, p["attn"]["q_norm"]["scale"], eps)
        k = _rms_norm(k, p["attn"]["k_norm"]["scale"], eps)
        if sliding:
            q, k = _rope(q, theta), _rope(k, theta)
        o = _attention(einsum, q, k, v,
                       config["sliding_window"] if sliding else seq, block)
        out = einsum("she,hed->sd", o * jax.nn.sigmoid(g),
                     p["attn"]["wo"]["kernel"])
        return x + _rms_norm(out, p["ln_post_attn"]["scale"], eps)

    def feed_forward_block(x, p, bias):
        m = _rms_norm(x, p["ln_mlp"]["scale"], eps)
        if "mlp" in p:
            out, counts = _swiglu(einsum, m, p["mlp"]), \
                jnp.zeros((routed,), jnp.int32)
        else:
            out, counts = routed_experts(config, einsum, m, p["moe"], bias,
                                         first_expert)
            out = out + _swiglu(einsum, m, p["moe"]["shared"])
        return x + _rms_norm(out, p["ln_post_mlp"]["scale"], eps), counts

    def stack(x, group, kinds, biases):
        """``kinds`` is one period; ``group`` holds ``layer_<i>`` of it
        with a leading axis over the period's repetitions, ``biases``
        each layer's expert_bias likewise."""
        attention = {kind: jax.checkpoint(
            lambda row, p, kind=kind: attention_row(row, p, kind))
            for kind in set(kinds)}
        feed_forward = jax.checkpoint(feed_forward_block)

        def period(x, layers_and_biases):
            layers, biases = layers_and_biases
            counts = {}
            for i, kind in enumerate(kinds):
                p, bias = layers[f"layer_{i}"], biases[f"layer_{i}"]
                x = jax.lax.map(lambda row: attention[kind](row, p), x)
                out, by_block = jax.lax.map(
                    lambda xb: feed_forward(xb, p, bias), blocks(x))
                x = out.reshape(x.shape)
                counts[f"layer_{i}"] = jnp.sum(by_block, axis=0)
            return x, counts

        return jax.lax.scan(period, x, (group, biases))

    @jax.checkpoint
    def head_block(x, targets, weight, scale, head):
        x = _rms_norm(x, scale, eps)
        logp = jax.nn.log_softmax(einsum("sd,vd->sv", x, head))
        picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
        return -jnp.sum(picked * weight)

    dense_kinds, dense_n, expert_kinds, expert_n = _periods(config)
    x = params["embed"][batch]
    if config["mup_enabled"]:
        x = x * math.sqrt(d)
    zero = jnp.zeros((routed,), jnp.float32)
    counts = {}
    if dense_n:
        x, _ = stack(x, params["dense_layers"], dense_kinds, {
            f"layer_{i}": jnp.tile(zero, (dense_n, 1))
            for i in range(len(dense_kinds))})
    if expert_n:
        x, counts = stack(x, params["periods"], expert_kinds, {
            f"layer_{i}": jnp.tile(zero, (expert_n, 1)) if aux is None
            else aux["router_state"]["periods"][f"layer_{i}"]["moe"][
                "expert_bias"] for i in range(len(expert_kinds))})
    # position t is scored on token t + 1; a row's last has no target
    targets = jnp.roll(batch, -1, axis=1)
    weight = jnp.ones(batch.shape).at[:, -1].set(0.0)
    loss = jnp.sum(jax.lax.map(
        lambda args: head_block(*args, params["ln_final"]["scale"],
                                params["lm_head"]),
        (blocks(x), blocks(targets), blocks(weight)))) / jnp.sum(weight)
    return loss if aux is None else (loss, counts)


def follow(config, workload, key, batch, steps, mode="float32"):
    """The first ``steps`` steps of training on the fixed ``batch``
    (rows, S), from the weights of ``key``.  Returns what the
    ``lm_train`` reference's ``follow`` returns: ``{"losses": [steps],
    "grad_norms": {leaf: norm of the first gradient}, "delta_norms":
    {leaf: norm of the parameters' change over the steps}}``, and with
    ``check_loss_after`` one more loss, after the last step; and
    ``"aux"``, every expert layer's expert_bias after the steps.

    One compiled program gives every step its loss and gradient (it
    takes the compiler two minutes, so it is not compiled again for
    each step as the ``lm_train`` reference's whole step is); AdamW's
    update from the gradients' history, elementwise, is a small program
    of its own for each length of the history."""
    einsum, _ = precision.products(mode)
    opt = workload["optimizer"]
    if opt["name"] != "adamw":
        raise NotImplementedError(f"optimizer {opt['name']!r}")
    spec = param_spec(config)
    loss_and_grad = jax.jit(jax.value_and_grad(
        lambda p, aux, b: batch_loss(config, einsum, p, b, aux),
        has_aux=True))
    update = jax.jit(lambda p, history: _adamw_update(opt, p, history),
                     donate_argnums=0)
    norms = jax.jit(weights.leaf_norms)
    params = jax.jit(lambda k: weights.make(k, spec))(key)
    aux = weights.make(key, aux_spec(config))
    history, found = (), {"losses": []}
    for _ in range(steps):
        (loss, counts), grads = loss_and_grad(params, aux, batch)
        aux = updated_bias(config, aux, counts)
        found["losses"].append(float(loss))
        history += (grads,)
        if len(history) == 1:
            found["grad_norms"] = jax.device_get(norms(grads))
        params = update(params, history)
    del history, grads
    found["delta_norms"] = jax.device_get(jax.jit(
        lambda p, k: weights.leaf_norms(jax.tree.map(
            lambda a, b: a - b, p, weights.make(k, spec))))(params, key))
    if workload.get("check_loss_after"):
        found["losses"].append(float(jax.jit(
            lambda p, aux, b: batch_loss(config, einsum, p, b, aux)[0])(
                params, aux, batch)))
    found["aux"] = jax.device_get(aux)
    return found
