"""Plain float32 reference of the ``granite_hybrid_train`` adapter:
Granite-4.0-H's layer (IBM, ``config.json`` ``granitemoehybrid`` +
``transformers``' ``modeling_granitemoehybrid.py`` and the Mamba-2 paper,
arXiv:2405.21060; the configuration's ``assumed`` says what was taken
from where) trained with AdamW on next-token cross-entropy, over the
slice of the vocabulary held here.

::

    x = embed[tokens] * embedding_multiplier
    every layer:  x = x + residual_multiplier * mixer(norm_1(x))
                  x = x + residual_multiplier * mlp(norm_2(x))
                  mlp(h) = (silu(h Wg) * (h Wu)) Wd
    "attention":  q, k, v = h Wq, h Wk, h Wv (grouped-query, no bias);
                  NO position encoding; causal over all keys;
                  softmax(q k^T * attention_multiplier) v;  Wo
    "mamba":      [z | xBC | dt] = h W_in
                  xBC = silu(causal_depthwise_conv(xBC) + b)
                  [x | B | C] = xBC
                  dt = softplus(dt + dt_bias);  A = -exp(A_log)
                  S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T   (S_0 = 0)
                  y_t = S_t C_t + D x_t
                  out = RMSNorm_w(y * silu(z)) W_out
    logits = (norm_final(x) @ embed^T) / logits_scaling

The scan is the TOKEN-BY-TOKEN recurrence, the definition: a
``lax.scan`` over the positions of a row that carries the (heads,
d_head, d_state) state, under ``jax.checkpoint`` in blocks of positions
so that its backward pass keeps one state a block and not one a position
(8,192 states of 2 MB would be 17 GB a layer).  No chunked algebra, no
cumulative sum, no decay matrix: what the program computes another way.
Attention forms its scores explicitly, a block of queries at a time.

Straightforward ``jax.numpy``: no kernel, no bfloat16, no fused loss,
nothing imported from the program.  The mixers take the rows one after
another, the MLP and the head blocks of tokens, so that it fits, alone,
on one chip at 8,192 tokens; ONE compiled program gives every step its
loss and gradient.  The norm and AdamW from the gradients' history are
the ``lm_train`` reference's.
"""

import math

import jax
import jax.numpy as jnp

from chipbench import ssm_flops, weights
from chipbench.references import precision
from chipbench.references.lm_train import _adamw_update, _rms_norm
from chipbench.weights import Leaf

#: positions a checkpointed block of the recurrence spans
SCAN_BLOCK = 128


def first_loss(config):
    """Seeded weights know nothing, so the first loss is ln(vocab) and
    half the variance of the logits: a normalised hidden state against
    rows of the tied embedding (std ``initializer_range``), divided by
    ``logits_scaling``."""
    return math.log(config["vocab_size"]) + config["hidden_size"] * (
        config["initializer_range"] / config["logits_scaling"]) ** 2 / 2


def _period(config):
    """(one period of layer kinds, its repeats), as the program stacks
    the layers: the shortest pattern whose repetition gives them."""
    kinds = list(config["layer_types"])
    n = len(kinds)
    if n != config["num_hidden_layers"] \
            or set(kinds) - {"mamba", "attention"}:
        raise ValueError("layer_types does not name num_hidden_layers "
                         "layers of kind mamba or attention")
    for p in range(1, n + 1):
        if n % p == 0 and kinds == kinds[:p] * (n // p):
            return kinds[:p], n // p


def mamba_sizes(config):
    """(heads, d_head, groups, d_state, d_inner, convolved channels)."""
    sizes = ssm_flops.mamba_sizes(config)
    if sizes[4] != config["mamba_expand"] * config["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not "
                         "mamba_expand x hidden_size")
    return sizes


def param_spec(config):
    d, hd = config["hidden_size"], config["hidden_size"] \
        // config["num_attention_heads"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    ff = config["shared_intermediate_size"]
    m_heads, _, _, _, inner, conv = mamba_sizes(config)
    taps = config["mamba_d_conv"]
    kinds, n = _period(config)
    if config["num_local_experts"] or config["mamba_proj_bias"] \
            or not config["mamba_conv_bias"] or config["attention_bias"]:
        raise NotImplementedError(
            "routed experts, a projection bias, a convolution without bias")

    def kernel(fan_in, *shape):
        return {"kernel": Leaf((n,) + shape, "normal",
                               1.0 / math.sqrt(fan_in))}

    def scale(width=d):
        return {"scale": Leaf((n, width), "ones")}

    def mlp():
        return {"wi_gate": kernel(d, d, ff), "wi_up": kernel(d, d, ff),
                "wo": kernel(ff, ff, d)}

    def mamba_layer():
        return {
            "ln_mamba": scale(), "ln_mlp": scale(), "mlp": mlp(),
            "mamba": {
                "in_proj": kernel(d, d, inner + conv + m_heads),
                "conv": {"kernel": Leaf((n, taps, conv), "normal",
                                        config["conv_initializer_std"]),
                         "bias": Leaf((n, conv), "normal",
                                      config["conv_initializer_std"])},
                "dt_bias": Leaf((n, m_heads), "zeros"),
                "A_log": Leaf((n, m_heads), "normal",
                              config["a_log_initializer_std"]),
                "D": Leaf((n, m_heads), "ones"),
                "gate_norm": scale(inner),
                "out_proj": kernel(inner, inner, d)}}

    def attention_layer():
        return {
            "ln_attn": scale(), "ln_mlp": scale(), "mlp": mlp(),
            "attn": {"wq": kernel(d, d, heads, hd),
                     "wk": kernel(d, d, kv, hd),
                     "wv": kernel(d, d, kv, hd),
                     "wo": kernel(heads * hd, heads, hd, d)}}

    return {
        "embed": Leaf((config["vocab_size"], d), "normal",
                      config["initializer_range"]),
        "ln_final": {"scale": Leaf((d,), "ones")},
        "periods": {f"layer_{i}": mamba_layer() if kind == "mamba"
                    else attention_layer() for i, kind in enumerate(kinds)},
    }


def aux_spec(config):
    """The training loop keeps nothing beside the parameters."""
    return None


def causal_conv_silu(x, kernel, bias):
    """x (S, C): channel c of position t reads its own channel at t - K
    + 1 .. t (zeros before the row's start) through ``kernel`` (K, C),
    plus ``bias``; then silu."""
    seq, taps = x.shape[0], kernel.shape[0]
    back = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return jax.nn.silu(bias + sum(back[k:k + seq] * kernel[k]
                                  for k in range(taps)))


def selective_scan(einsum, x, dt, a, b, c, block=SCAN_BLOCK):
    """The recurrence, a position at a time.  x (S, H, P), dt (S, H), a
    (H,), b and c (S, G, N); head h reads group h // (H / G).  Returns
    y (S, H, P) with ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``,
    ``y_t = S_t C_t``, the state zero before the row's first
    position."""
    seq, heads, width = x.shape
    groups, state = b.shape[1:]
    each = heads // groups
    block = math.gcd(seq, block)
    x = x.reshape(seq, groups, each, width)
    dt = dt.reshape(seq, groups, each)
    a = a.reshape(groups, each)

    def position(carried, inputs):
        x_t, dt_t, b_t, c_t = inputs
        carried = jnp.exp(dt_t * a)[..., None, None] * carried \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return carried, einsum("grpn,gn->grp", carried, c_t)

    @jax.checkpoint
    def positions(carried, inputs):
        return jax.lax.scan(position, carried, inputs)

    _, y = jax.lax.scan(
        positions, jnp.zeros((groups, each, width, state), jnp.float32),
        jax.tree.map(lambda t: t.reshape((seq // block, block) + t.shape[1:]),
                     (x, dt, b, c)))
    return y.reshape(seq, heads, width)


def mamba_mixer(config, einsum, h, p):
    """The state-space mixer of one row ``h`` (S, d) with the layer's
    parameters ``p["mamba"]``."""
    heads, width, groups, state, inner, conv = mamba_sizes(config)
    seq = h.shape[0]
    z, xbc, dt = jnp.split(einsum("sd,de->se", h, p["in_proj"]["kernel"]),
                           (inner, inner + conv), axis=-1)
    xbc = causal_conv_silu(xbc, p["conv"]["kernel"], p["conv"]["bias"])
    x, b, c = jnp.split(xbc, (inner, inner + groups * state), axis=-1)
    x = x.reshape(seq, heads, width)
    y = selective_scan(einsum, x, jax.nn.softplus(dt + p["dt_bias"]),
                       -jnp.exp(p["A_log"]),
                       b.reshape(seq, groups, state),
                       c.reshape(seq, groups, state))
    y = (y + p["D"][:, None] * x).reshape(seq, inner)
    y = _rms_norm(y * jax.nn.silu(z), p["gate_norm"]["scale"],
                  config["rms_norm_eps"])
    return einsum("se,ed->sd", y, p["out_proj"]["kernel"])


def attention(config, einsum, q, k, v, block):
    """q (S, H, D) against k, v (S, KV, D): query head h reads kv head
    h // (H / KV); position t sees positions 0 .. t; the scores are the
    products times ``attention_multiplier``.  A block of query rows at
    a time, so that one block's scores are all that lives."""
    seq, heads, hd = q.shape
    kv = k.shape[1]
    qg = q.reshape(seq // block, block, kv, heads // kv, hd)
    k_pos = jnp.arange(seq)[None, :]

    @jax.checkpoint
    def one_block(args):
        qb, start = args
        scores = einsum("tkgd,skd->kgts", qb, k) \
            * config["attention_multiplier"]
        mask = start + jnp.arange(block)[:, None] >= k_pos
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return einsum("kgts,skd->tkgd", probs, v)

    out = jax.lax.map(one_block,
                      (qg, jnp.arange(0, seq, block, dtype=jnp.int32)))
    return out.reshape(seq, heads, hd)


def attention_mixer(config, einsum, h, p, block):
    """The attention mixer of one row ``h`` (S, d): no position
    encoding."""
    q = einsum("sd,dhe->she", h, p["wq"]["kernel"])
    k = einsum("sd,dhe->she", h, p["wk"]["kernel"])
    v = einsum("sd,dhe->she", h, p["wv"]["kernel"])
    o = attention(config, einsum, q, k, v, block)
    return einsum("she,hed->sd", o, p["wo"]["kernel"])


def mixer_row(config, einsum, x, p, kind, block):
    """``x + residual_multiplier * mixer(norm_1(x))`` of one row."""
    eps = config["rms_norm_eps"]
    if kind == "mamba":
        mixed = mamba_mixer(config, einsum,
                            _rms_norm(x, p["ln_mamba"]["scale"], eps),
                            p["mamba"])
    else:
        mixed = attention_mixer(config, einsum,
                                _rms_norm(x, p["ln_attn"]["scale"], eps),
                                p["attn"], block)
    return x + config["residual_multiplier"] * mixed


def mlp_tokens(config, einsum, x, p):
    """``x + residual_multiplier * mlp(norm_2(x))`` of tokens (T, d)."""
    h = _rms_norm(x, p["ln_mlp"]["scale"], config["rms_norm_eps"])
    gate = jax.nn.silu(einsum("sd,df->sf", h, p["mlp"]["wi_gate"]["kernel"]))
    up = einsum("sd,df->sf", h, p["mlp"]["wi_up"]["kernel"])
    return x + config["residual_multiplier"] * einsum(
        "sf,fd->sd", gate * up, p["mlp"]["wo"]["kernel"])


def batch_loss(config, einsum, params, batch):
    """Mean next-token cross-entropy of a batch of token rows (R, S)."""
    seq = batch.shape[1]
    block = math.gcd(seq, 512)
    if not config["tie_word_embeddings"] \
            or config["position_embedding_type"] != "nope":
        raise NotImplementedError("an untied head, a position encoding")

    def blocks(x):
        return x.reshape((-1, block) + x.shape[2:])

    kinds, _ = _period(config)
    mixers = {kind: jax.checkpoint(
        lambda row, p, kind=kind: mixer_row(config, einsum, row, p, kind,
                                            block))
        for kind in set(kinds)}
    mlp_block = jax.checkpoint(
        lambda xb, p: mlp_tokens(config, einsum, xb, p))

    def period(x, layers):
        for i, kind in enumerate(kinds):
            p = layers[f"layer_{i}"]
            x = jax.lax.map(lambda row: mixers[kind](row, p), x)
            x = jax.lax.map(lambda xb: mlp_block(xb, p),
                            blocks(x)).reshape(x.shape)
        return x, None

    @jax.checkpoint
    def head_block(x, targets, weight, scale, embed):
        x = _rms_norm(x, scale, config["rms_norm_eps"])
        logp = jax.nn.log_softmax(einsum("sd,vd->sv", x, embed)
                                  / config["logits_scaling"])
        picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
        return -jnp.sum(picked * weight)

    x, _ = jax.lax.scan(
        period, params["embed"][batch] * config["embedding_multiplier"],
        params["periods"])
    # position t is scored on token t + 1; a row's last has no target
    targets = jnp.roll(batch, -1, axis=1)
    weight = jnp.ones(batch.shape).at[:, -1].set(0.0)
    return jnp.sum(jax.lax.map(
        lambda args: head_block(*args, params["ln_final"]["scale"],
                                params["embed"]),
        (blocks(x), blocks(targets), blocks(weight)))) / jnp.sum(weight)


def follow(config, workload, key, batch, steps, mode="float32"):
    """The first ``steps`` steps of training on the fixed ``batch``
    (rows, S), from the weights of ``key``.  Returns what the
    ``lm_train`` reference's ``follow`` returns: ``{"losses": [steps],
    "grad_norms": {leaf: norm of the first gradient}, "delta_norms":
    {leaf: norm of the parameters' change over the steps}}``, and with
    ``check_loss_after`` one more loss, after the last step.

    One compiled program gives every step its loss and gradient;
    AdamW's update from the gradients' history, elementwise, is a small
    program of its own for each length of the history."""
    einsum, _ = precision.products(mode)
    opt = workload["optimizer"]
    if opt["name"] != "adamw":
        raise NotImplementedError(f"optimizer {opt['name']!r}")
    spec = param_spec(config)
    loss_and_grad = jax.jit(jax.value_and_grad(
        lambda p, b: batch_loss(config, einsum, p, b)))
    update = jax.jit(lambda p, history: _adamw_update(opt, p, history),
                     donate_argnums=0)
    norms = jax.jit(weights.leaf_norms)
    params = jax.jit(lambda k: weights.make(k, spec))(key)
    history, found = (), {"losses": []}
    for _ in range(steps):
        loss, grads = loss_and_grad(params, batch)
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
            lambda p, b: batch_loss(config, einsum, p, b))(params, batch)))
    return found
