"""Plain float32 reference of the ``lm_train`` adapter: a decoder-only
language model (pre-RMSNorm, rotary positions, grouped-query attention
under a causal sliding window, SwiGLU, the output head tied to the
embedding) trained with AdamW on next-token cross-entropy.

Straightforward ``jax.numpy``: no kernel, no bfloat16, no fused loss,
nothing imported from the program.  It makes its own weights from the
key (``chipbench/weights.py``), follows the first steps of training row
by row so that it fits, alone, on one chip, and returns the
numbers ``run.py`` compares with the program's (the limits are in
``chipbench/limits/<cell>.json``).

Departures from the published Mistral-7B that the configuration lists
(tied head, RMSNorm epsilon) are read from the configuration file, so
the reference computes what the file says is run.
"""

import math

import jax
import jax.numpy as jnp

from chipbench import weights
from chipbench.references import precision
from chipbench.weights import Leaf

def first_loss(config):
    """Seeded weights know nothing, so the first loss is ln(vocab) and
    half the variance of the logits: a normalised hidden state (norm
    sqrt(d)) against rows of the tied embedding (std
    ``initializer_range``)."""
    return math.log(config["vocab_size"]) \
        + config["hidden_size"] * config["initializer_range"] ** 2 / 2


def param_spec(config):
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, ff = config["head_dim"], config["intermediate_size"]

    def kernel(fan_in, *shape):
        return {"kernel": Leaf((layers,) + shape, "normal",
                               1.0 / math.sqrt(fan_in))}

    def scale(*lead):
        return {"scale": Leaf(lead + (d,), "ones")}

    return {
        "embed": Leaf((config["vocab_size"], d), "normal",
                      config["initializer_range"]),
        "layers": {
            "attn": {"wq": kernel(d, d, heads, hd),
                     "wk": kernel(d, d, kv, hd),
                     "wv": kernel(d, d, kv, hd),
                     "wo": kernel(heads * hd, heads, hd, d)},
            "ln_attn": scale(layers),
            "ln_mlp": scale(layers),
            "mlp": {"wi_gate": kernel(d, d, ff),
                    "wi_up": kernel(d, d, ff),
                    "wo": kernel(ff, ff, d)},
        },
        "ln_final": scale(),
    }


def aux_spec(config):
    return None


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """x (S, H, D): rotate channel i with channel i + D/2."""
    seq, _, hd = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    sin, cos = jnp.sin(angles)[:, None], jnp.cos(angles)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(einsum, q, k, v, window, block):
    """q (S, H, D) against k, v (S, KV, D): query head h reads kv head
    h // (H / KV); position t sees positions (t - window, t].  Done in
    blocks of query rows so the scores of one block are all that
    lives."""
    seq, heads, hd = q.shape
    kv = k.shape[1]
    qg = q.reshape(seq // block, block, kv, heads // kv, hd)
    k_pos = jnp.arange(seq)[None, :]

    @jax.checkpoint
    def one_block(args):
        qb, start = args
        scores = einsum("tkgd,skd->kgts", qb, k) / math.sqrt(hd)
        q_pos = start + jnp.arange(block)[:, None]
        mask = (q_pos >= k_pos) & (q_pos - k_pos < window)
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return einsum("kgts,skd->tkgd", probs, v)

    out = jax.lax.map(one_block,
                      (qg, jnp.arange(0, seq, block, dtype=jnp.int32)))
    return out.reshape(seq, heads, hd)


def batch_loss(config, einsum, params, batch):
    """Mean next-token cross-entropy of a batch of token rows (R, S).

    Attention takes the rows one after another, the MLP and the head
    blocks of tokens (``lax.map`` over checkpointed functions): the
    backward pass then sums weight gradients into an accumulator the
    size of one layer's MLP, never a second copy of the whole
    model's."""
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    seq = batch.shape[1]
    window = config["sliding_window"] or seq
    block = math.gcd(seq, 512)
    if not config["tie_word_embeddings"]:
        raise NotImplementedError("an untied output head")

    def blocks(x):
        """(R, S, ...) -> (R * S / block, block, ...): what works on
        tokens one by one takes a block of them at a time, whatever
        their row."""
        return x.reshape((-1, block) + x.shape[2:])

    @jax.checkpoint
    def attention_row(x, p):
        h = _rms_norm(x, p["ln_attn"]["scale"], eps)
        q = _rope(einsum("sd,dhe->she", h, p["attn"]["wq"]["kernel"]), theta)
        k = _rope(einsum("sd,dhe->she", h, p["attn"]["wk"]["kernel"]), theta)
        v = einsum("sd,dhe->she", h, p["attn"]["wv"]["kernel"])
        o = _attention(einsum, q, k, v, window, block)
        return x + einsum("she,hed->sd", o, p["attn"]["wo"]["kernel"])

    @jax.checkpoint
    def mlp_block(x, p):
        h = _rms_norm(x, p["ln_mlp"]["scale"], eps)
        gate = jax.nn.silu(einsum("sd,df->sf", h,
                                  p["mlp"]["wi_gate"]["kernel"]))
        up = einsum("sd,df->sf", h, p["mlp"]["wi_up"]["kernel"])
        return x + einsum("sf,fd->sd", gate * up, p["mlp"]["wo"]["kernel"])

    @jax.checkpoint
    def layer(x, p):
        x = jax.lax.map(lambda row: attention_row(row, p), x)
        return jax.lax.map(lambda xb: mlp_block(xb, p),
                           blocks(x)).reshape(x.shape), None

    @jax.checkpoint
    def head_block(x, targets, weight, scale, embed):
        x = _rms_norm(x, scale, eps)
        logp = jax.nn.log_softmax(einsum("sd,vd->sv", x, embed))
        picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
        return -jnp.sum(picked * weight)

    # position t is scored on token t + 1; a row's last has no target
    targets = jnp.roll(batch, -1, axis=1)
    weight = jnp.ones(batch.shape).at[:, -1].set(0.0)
    x, _ = jax.lax.scan(layer, params["embed"][batch], params["layers"])
    return jnp.sum(jax.lax.map(
        lambda args: head_block(*args, params["ln_final"]["scale"],
                                params["embed"]),
        (blocks(x), blocks(targets), blocks(weight)))) / jnp.sum(weight)


def _adamw_update(opt, params, history):
    """AdamW's step number ``len(history)`` from the gradients of all
    steps so far, oldest first: the moments are sums over the history,
    so between steps the reference keeps gradients and no moments (one
    model's worth less of memory while the next gradient is made)."""
    b1, b2, t = opt["b1"], opt["b2"], len(history)

    def leaf(p, *grads):
        mu = sum((1 - b1) * b1 ** (t - 1 - i) * g
                 for i, g in enumerate(grads))
        nu = sum((1 - b2) * b2 ** (t - 1 - i) * g * g
                 for i, g in enumerate(grads))
        update = (mu / (1 - b1 ** t)) / (
            jnp.sqrt(nu / (1 - b2 ** t)) + opt["eps"])
        return p - opt["learning_rate"] * (update + opt["weight_decay"] * p)

    return jax.tree.map(leaf, params, *history)


def step_fn(config, workload, mode="float32"):
    """``one_step(params, history, key, batch, last)``: one step of
    training from ``params`` (``None``: the seeded weights of ``key``)
    and the gradients of the steps before it.  The last step hands
    no parameters on, unless the mix asks for the loss after it."""
    einsum, _ = precision.products(mode)
    opt = workload["optimizer"]
    if opt["name"] != "adamw":
        raise NotImplementedError(f"optimizer {opt['name']!r}")
    spec = param_spec(config)
    keep = bool(workload.get("check_loss_after"))
    batch_grad = jax.value_and_grad(
        lambda p, b: batch_loss(config, einsum, p, b))

    def one_step(params, history, key, batch, last):
        if params is None:
            params = weights.make(key, spec)
        loss, grads = batch_grad(params, batch)
        history = history + (grads,)
        params = _adamw_update(opt, params, history)
        out = {"loss": loss}
        if len(history) == 1:
            out["grad_norms"] = weights.leaf_norms(grads)
        if last:
            out["delta_norms"] = weights.leaf_norms(jax.tree.map(
                lambda a, b: a - b, params, weights.make(key, spec)))
            return out, params if keep else None, None
        return out, params, history

    return one_step


def follow(config, workload, key, batch, steps, mode="float32"):
    """The first ``steps`` steps of training on the fixed ``batch``
    (rows, S), from the weights of ``key``; one jitted program a step.

    Returns, on the host, ``{"losses": [steps], "grad_norms": {leaf:
    norm of the first gradient}, "delta_norms": {leaf: norm of the
    parameters' change over the steps}}``.  Where the mix sets
    ``check_loss_after``, ``losses`` has one more: the loss after the
    last step, a forward pass alone, which costs a fifth of a step and
    says where the last update led (its sign and its size; the norms
    say neither).
    """
    one_step = step_fn(config, workload, mode)
    params, history, found = None, (), {"losses": []}
    for i in range(steps):
        last = i == steps - 1
        out, params, history = jax.jit(
            one_step, static_argnums=4,
            donate_argnums=() if last else (0, 1))(
                params, history, key, batch, last)
        out = jax.device_get(out)
        found["losses"].append(float(out.pop("loss")))
        found.update(out)
    if params is not None:
        einsum, _ = precision.products(mode)
        found["losses"].append(float(jax.jit(
            lambda p, b: batch_loss(config, einsum, p, b))(params, batch)))
    return found
