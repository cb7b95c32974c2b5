"""Plain float32 reference of the ``kimi_linear_train`` adapter:
Kimi-Linear's layer (moonshotai, ``config.json`` ``kimi_linear`` +
``modeling_kimi.py``, ``fla``'s ``KimiDeltaAttention`` and
arXiv:2510.26692; the configuration's ``assumed`` says what was taken
from where) trained with AdamW on next-token cross-entropy, for ONE
chip's share of an expert-parallel deployment: the router scores all the
published experts, of ``sum_j w_j SwiGLU_{idx_j}(m)`` only the terms of
the experts held here are computed, and the vocabulary is the slice held
here.

::

    x = embed[tokens]
    every layer:  x = x + mixer(norm_1(x));  x = x + FF(norm_2(x))
    "kda":  q = l2norm(silu(conv(h Wq)));  k = l2norm(silu(conv(h Wk)))
            v = silu(conv(h Wv))          # causal depthwise, 4 taps, no bias
            g = -exp(A_log_h) * softplus((h Wf_a) Wf_b + dt_bias)
            beta = sigmoid(h Wb)
            S <- exp(g_t)[:, None] * S;  u = beta_t (v_t - S^T k_t)
            S <- S + k_t u^T;  o_t = S^T (q_t / sqrt(d_k))     (S_0 = 0)
            out = (rmsnorm_head(o) * sigmoid((h Wg_a) Wg_b)) Wo
    "mla":  q = h Wq -> H heads of 192;  [c | k_s] = h Wkv_a (512 | 64)
            [k_n | v] = rmsnorm(c) Wkv_b -> H heads of (128 | 128)
            k = [k_n | k_s for every head];  NO position encoding
            out = concat(softmax_causal(q k^T / sqrt(192)) v) Wo
    FF, the leading dense layers:  SwiGLU(m), width intermediate_size
    FF, expert layers: s = sigmoid(m Wr); idx = top_k(s + b); w =
      s[idx] / (sum s[idx] + 1e-20) * routed_scaling_factor;
      SwiGLU_shared(m) + sum_{j: idx_j held} w_j SwiGLU_{idx_j}(m)
    logits = norm_final(x) @ lm_head^T

The delta rule is the TOKEN-BY-TOKEN recurrence, the definition: a
``lax.scan`` over the positions of a row that carries the (heads, d_k,
d_v) state, under ``jax.checkpoint`` in blocks of positions so that its
backward pass keeps one state a block and not one a position (8,192
states of 2 MB would be 17 GB a layer).  No chunk, no cumulative sum, no
triangular solve: what the program computes another way.  Latent
attention forms its scores explicitly at the published widths (192-wide
queries and keys, 128-wide values, nothing padded), a block of queries
at a time.  ``b`` (expert_bias) and the routed experts are the
``afmoe_train`` reference's.

Straightforward ``jax.numpy``: no kernel, no bfloat16, no fused loss,
nothing imported from the program.  The mixers take the rows one after
another, the feed-forward and the head blocks of tokens, so that it
fits, alone, on one chip at 2 x 8,192 tokens; ONE compiled program gives
every step its loss and gradient.
"""

import math

import jax
import jax.numpy as jnp

from chipbench import weights
from chipbench.kimi_linear_flops import kda_sizes
from chipbench.references import precision
from chipbench.references.afmoe_train import (
    _swiglu, routed_experts, updated_bias)
from chipbench.references.lm_train import _adamw_update, _rms_norm
from chipbench.weights import Leaf

#: positions a checkpointed block of the recurrence spans
SCAN_BLOCK = 128
#: the program's names of the published kinds
KINDS = ("kda", "mla")


def first_loss(config):
    """Seeded weights know nothing, so the first loss is ln(vocab) and
    half the variance of the logits: a normalised hidden state (norm
    sqrt(d)) against rows of the head (std ``initializer_range``)."""
    return math.log(config["vocab_size"]) \
        + config["hidden_size"] * config["initializer_range"] ** 2 / 2


def _shortest(group):
    n = len(group)
    for p in range(1, n + 1):
        if n % p == 0 and group == group[:p] * (n // p):
            return group[:p], n // p
    return [], 0


def _periods(config):
    """(kinds of the leading dense layers' one period, its repeats,
    kinds of the expert layers' period, its repeats), as the program
    stacks them."""
    kinds, lead = list(config["layer_types"]), config["first_k_dense_replace"]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError("layer_types does not name num_hidden_layers "
                         f"layers of kind {KINDS}")
    return _shortest(kinds[:lead]) + _shortest(kinds[lead:])


def param_spec(config):
    d = config["hidden_size"]
    heads = config["num_attention_heads"]
    rank, nope = config["kv_lora_rank"], config["qk_nope_head_dim"]
    shared_key, value = config["qk_rope_head_dim"], config["v_head_dim"]
    k_heads, k_width, inner, taps = kda_sizes(config)
    ff, width = config["intermediate_size"], config["moe_intermediate_size"]
    held, routed = config["num_experts"], config["published"]["num_experts"]
    shared = width * config["num_shared_experts"]
    if config["q_lora_rank"] is not None or not config["mla_use_nope"] \
            or config["tie_word_embeddings"]:
        raise NotImplementedError(
            "a low-rank query projection, rotary positions, a tied head")
    dense_kinds, dense_n, expert_kinds, expert_n = _periods(config)

    def layer(n, kind, feed_forward):
        def normal(fan_in, *shape):
            return Leaf((n,) + shape, "normal", 1.0 / math.sqrt(fan_in))

        def kernel(fan_in, *shape):
            return {"kernel": normal(fan_in, *shape)}

        def scale(size):
            return {"scale": Leaf((n, size), "ones")}

        def swiglu(f):
            return {"wi_gate": kernel(d, d, f), "wi_up": kernel(d, d, f),
                    "wo": kernel(f, f, d)}

        def conv():
            return {"kernel": Leaf((n, taps, inner), "normal",
                                   config["conv_initializer_std"])}

        out = {"ln_mlp": scale(d)}
        if kind == "kda":
            out["ln_kda"] = scale(d)
            out["kda"] = {
                "wq": kernel(d, d, inner), "wk": kernel(d, d, inner),
                "wv": kernel(d, d, inner),
                "f_a": kernel(d, d, k_width),
                "f_b": kernel(k_width, k_width, inner),
                "g_a": kernel(d, d, k_width),
                "g_b": kernel(k_width, k_width, inner),
                "b_proj": kernel(d, d, k_heads),
                "conv_q": conv(), "conv_k": conv(), "conv_v": conv(),
                # the stand-in draw (the configuration's ``departures``)
                "dt_bias": Leaf((n, inner), "normal",
                                config["dt_bias_initializer_std"]),
                "A_log": Leaf((n, k_heads), "normal",
                              config["a_log_initializer_std"]),
                "gate_norm": scale(k_width),
                "out_proj": kernel(inner, inner, d)}
        else:
            out["ln_attn"] = scale(d)
            out["attn"] = {
                "wq": kernel(d, d, heads, nope + shared_key),
                "kv_a": kernel(d, d, rank + shared_key),
                "kv_norm": scale(rank),
                "kv_b": kernel(rank, rank, heads, nope + value),
                "wo": kernel(heads * value, heads, value, d)}
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
        spec["dense_layers"] = {
            f"layer_{i}": layer(dense_n, kind, "dense")
            for i, kind in enumerate(dense_kinds)}
    if expert_n:
        spec["periods"] = {
            f"layer_{i}": layer(expert_n, kind, "experts")
            for i, kind in enumerate(expert_kinds)}
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


def _router_config(config):
    """The routed layer's keys under the names the ``afmoe_train``
    reference reads them by."""
    return {"num_experts_per_tok": config["num_experts_per_token"],
            "route_scale": config["routed_scaling_factor"],
            "load_balance_coeff": config["load_balance_coeff"]}


def causal_conv_silu(x, kernel):
    """x (S, C): channel c of position t reads its own channel at t - K
    + 1 .. t (zeros before the row's start) through ``kernel`` (K, C);
    then silu.  No bias."""
    seq, taps = x.shape[0], kernel.shape[0]
    back = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(back[k:k + seq] * kernel[k]
                           for k in range(taps)))


def l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def delta_rule(einsum, q, k, v, g, beta, block=SCAN_BLOCK):
    """The recurrence, a position at a time.  q, k, g (S, H, K), v (S,
    H, V), beta (S, H).  Returns o (S, H, V) with ``S <- exp(g_t)[:,
    None] * S; u = beta_t (v_t - S^T k_t); S <- S + k_t u^T; o_t = S^T
    q_t``, the state zero before the row's first position."""
    seq, heads, width = k.shape
    block = math.gcd(seq, block)

    def position(state, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs
        state = jnp.exp(g_t)[..., None] * state
        u = beta_t[:, None] * (v_t - einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[..., None] * u[:, None, :]
        return state, einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def positions(state, inputs):
        return jax.lax.scan(position, state, inputs)

    _, o = jax.lax.scan(
        positions, jnp.zeros((heads, width, v.shape[-1]), jnp.float32),
        jax.tree.map(lambda t: t.reshape((seq // block, block) + t.shape[1:]),
                     (q, k, v, g, beta)))
    return o.reshape(seq, heads, -1)


def kda_mixer(config, einsum, h, p):
    """The delta-rule mixer of one row ``h`` (S, d) with the layer's
    parameters ``p["kda"]``."""
    heads, width, _, _ = kda_sizes(config)
    seq = h.shape[0]

    def projected(name):
        return einsum("sd,de->se", h, p[name]["kernel"])

    def mixed(name, conv):
        return causal_conv_silu(projected(name), p[conv]["kernel"]).reshape(
            seq, heads, width)

    q = l2norm(mixed("wq", "conv_q")) / math.sqrt(width)
    k = l2norm(mixed("wk", "conv_k"))
    v = mixed("wv", "conv_v")
    decay = einsum("sr,re->se", projected("f_a"), p["f_b"]["kernel"])
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        decay + p["dt_bias"]).reshape(seq, heads, width)
    beta = jax.nn.sigmoid(projected("b_proj"))
    gate = einsum("sr,re->se", projected("g_a"), p["g_b"]["kernel"])
    o = delta_rule(einsum, q, k, v, g, beta)
    o = _rms_norm(o, p["gate_norm"]["scale"], config["rms_norm_eps"]) \
        * jax.nn.sigmoid(gate.reshape(seq, heads, width))
    return einsum("se,ed->sd", o.reshape(seq, heads * width),
                  p["out_proj"]["kernel"])


def mla_mixer(config, einsum, h, p, block):
    """Latent attention of one row ``h`` (S, d) with the layer's
    parameters ``p["attn"]``: no position encoding, a block of query
    rows at a time so that one block's scores are all that lives."""
    rank, nope = config["kv_lora_rank"], config["qk_nope_head_dim"]
    seq = h.shape[0]
    q = einsum("sd,dhe->she", h, p["wq"]["kernel"])
    latent, k_shared = jnp.split(einsum("sd,de->se", h, p["kv_a"]["kernel"]),
                                 (rank,), axis=-1)
    latent = _rms_norm(latent, p["kv_norm"]["scale"], config["rms_norm_eps"])
    k_own, v = jnp.split(einsum("sr,rhe->she", latent, p["kv_b"]["kernel"]),
                         (nope,), axis=-1)
    heads, width = q.shape[1:]
    k = jnp.concatenate([k_own, jnp.broadcast_to(
        k_shared[:, None, :], (seq, heads, k_shared.shape[-1]))], axis=-1)
    k_pos = jnp.arange(seq)[None, :]

    @jax.checkpoint
    def one_block(args):
        qb, start = args
        scores = einsum("thd,shd->hts", qb, k) / math.sqrt(width)
        mask = start + jnp.arange(block)[:, None] >= k_pos
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return einsum("hts,shd->thd", probs, v)

    o = jax.lax.map(one_block, (
        q.reshape(seq // block, block, heads, width),
        jnp.arange(0, seq, block, dtype=jnp.int32)))
    return einsum("she,hed->sd", o.reshape(seq, heads, -1),
                  p["wo"]["kernel"])


def mixer_row(config, einsum, x, p, kind, block):
    """``x + mixer(norm_1(x))`` of one row."""
    eps = config["rms_norm_eps"]
    if kind == "kda":
        return x + kda_mixer(config, einsum,
                             _rms_norm(x, p["ln_kda"]["scale"], eps),
                             p["kda"])
    return x + mla_mixer(config, einsum,
                         _rms_norm(x, p["ln_attn"]["scale"], eps),
                         p["attn"], block)


def feed_forward_block(config, einsum, x, p, bias):
    """``x + FF(norm_2(x))`` of tokens (T, d), and how many of them
    chose each of the router's experts."""
    routed = config["published"]["num_experts"]
    first_expert = config.get("deployment", {}).get("first_expert_held", 0)
    m = _rms_norm(x, p["ln_mlp"]["scale"], config["rms_norm_eps"])
    if "mlp" in p:
        return x + _swiglu(einsum, m, p["mlp"]), \
            jnp.zeros((routed,), jnp.int32)
    out, counts = routed_experts(_router_config(config), einsum, m,
                                 p["moe"], bias, first_expert)
    return x + out + _swiglu(einsum, m, p["moe"]["shared"]), counts


def batch_loss(config, einsum, params, batch, aux=None):
    """Mean next-token cross-entropy of a batch of token rows (R, S),
    and, with ``aux`` (``aux_spec``'s tree: the expert_bias of every
    expert layer; zero without it), ``(loss, {layer: tokens by expert
    (repeats, experts)})``."""
    routed = config["published"]["num_experts"]
    if config["moe_router_activation_func"] != "sigmoid" \
            or not config["moe_renormalize"] \
            or config["num_expert_group"] != 1 or config["topk_group"] != 1:
        raise NotImplementedError(
            "a router that is not a sigmoid with renormalised weights "
            "over one group")
    seq = batch.shape[1]
    block = math.gcd(seq, 512)

    def blocks(x):
        return x.reshape((-1, block) + x.shape[2:])

    def stack(x, group, kinds, biases):
        """``kinds`` is one period; ``group`` holds ``layer_<i>`` of it
        with a leading axis over the period's repetitions, ``biases``
        each layer's expert_bias likewise."""
        mixers = {kind: jax.checkpoint(
            lambda row, p, kind=kind: mixer_row(config, einsum, row, p,
                                                kind, block))
            for kind in set(kinds)}
        feed_forward = jax.checkpoint(
            lambda xb, p, bias: feed_forward_block(config, einsum, xb, p,
                                                   bias))

        def period(x, layers_and_biases):
            layers, biases = layers_and_biases
            counts = {}
            for i, kind in enumerate(kinds):
                p, bias = layers[f"layer_{i}"], biases[f"layer_{i}"]
                x = jax.lax.map(lambda row: mixers[kind](row, p), x)
                out, by_block = jax.lax.map(
                    lambda xb: feed_forward(xb, p, bias), blocks(x))
                x = out.reshape(x.shape)
                counts[f"layer_{i}"] = jnp.sum(by_block, axis=0)
            return x, counts

        return jax.lax.scan(period, x, (group, biases))

    @jax.checkpoint
    def head_block(x, targets, weight, scale, head):
        x = _rms_norm(x, scale, config["rms_norm_eps"])
        logp = jax.nn.log_softmax(einsum("sd,vd->sv", x, head))
        picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
        return -jnp.sum(picked * weight)

    dense_kinds, dense_n, expert_kinds, expert_n = _periods(config)
    x = params["embed"][batch]
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

    One compiled program gives every step its loss and gradient;
    AdamW's update from the gradients' history, elementwise, is a small
    program of its own for each length of the history."""
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
        aux = updated_bias(_router_config(config), aux, counts)
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
