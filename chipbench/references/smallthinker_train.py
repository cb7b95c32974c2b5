"""Plain float32 reference of the ``smallthinker_train`` adapter:
SmallThinker's layer (PowerInfer, ``config.json`` +
``modeling_smallthinker.py``; the configuration's ``assumed`` says what
was taken from where) trained with AdamW on next-token cross-entropy
plus the router's load-balancing loss, for ONE chip's share of an
expert-parallel deployment: the router scores all the published
experts, and of ``sum_j w_j ReGLU_{idx_j}(m)`` only the terms of the
experts held here are computed; the vocabulary is the slice held here.

Per layer ``l`` (all alike in form; ``sliding_window_layout[l] == 0``
is a global layer, which also has ``rope_layout[l] == 0``)::

    r   = x Wr                     # router logits from the layer's INPUT
    a   = Attn_l(norm_1(x));  x' = x + a
    m   = norm_2(x')
    idx = top_k(r);  w = softmax(r[idx])
    y   = sum_{j: idx_j held} w_j (relu(m Wg_j) * (m Wu_j)) Wd_j
    out = x' + y
    Attn: q, k, v = a Wq, a Wk, a Wv (no bias, no head norm, no gate);
      global layer: causal over all keys, NO position encoding;
      other layers: the last ``sliding_window_size`` keys, rotary

then ``norm_final`` and the untied head; the embedding is not scaled.
Loss = cross-entropy + ``router_aux_loss_coef`` x the mean over the
layers of ``L_aux = E sum_e f_e P_e`` (arXiv:2101.03961 eq. 4-6): ``f_e``
the share of the batch's assignments on expert e (no gradient), ``P_e``
the mean over the batch's tokens of ``softmax(r)_e`` over all E.

Straightforward ``jax.numpy``: no kernel, no bfloat16, no fused loss, no
sorting of tokens (every held expert sees every token of a block, times
its weight or zero), nothing imported from the program.  Attention
takes the rows one after another, everything else blocks of tokens, so
that it fits, alone, on one chip at 2 x 8,192 tokens; ONE compiled
program gives every step its loss and gradient.  The pieces that do not
depend on the architecture (the norm, the rotary positions, the masked
attention in blocks, AdamW from the gradients' history) are the
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
    half the variance of the logits (a normalised hidden state against
    rows of the head, std ``initializer_range``), and the balance loss
    of a router that knows nothing either: 1.0 a layer."""
    return math.log(config["vocab_size"]) \
        + config["hidden_size"] * config["initializer_range"] ** 2 / 2 \
        + config["router_aux_loss_coef"]


def layer_kinds(config):
    """Per layer ``(windowed, rotary)`` from the published layouts."""
    kinds = list(zip(config["sliding_window_layout"], config["rope_layout"]))
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("the layouts do not name num_hidden_layers layers")
    return [(bool(w), bool(r)) for w, r in kinds]


def _period(config):
    """(one period of layer kinds, its repeats), as the program stacks
    the layers: the shortest pattern whose repetition gives them."""
    kinds = layer_kinds(config)
    n = len(kinds)
    for p in range(1, n + 1):
        if n % p == 0 and kinds == kinds[:p] * (n // p):
            return kinds[:p], n // p


def param_spec(config):
    d, hd = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    width = config["moe_ffn_hidden_size"]
    held = config["moe_num_primary_experts"]
    routed = config["published"]["moe_num_primary_experts"]
    kinds, n = _period(config)

    def normal(fan_in, *shape):
        return Leaf((n,) + shape, "normal", 1.0 / math.sqrt(fan_in))

    def kernel(fan_in, *shape):
        return {"kernel": normal(fan_in, *shape)}

    def scale():
        return {"scale": Leaf((n, d), "ones")}

    def layer():
        return {
            "attn": {"wq": kernel(d, d, heads, hd),
                     "wk": kernel(d, d, kv, hd),
                     "wv": kernel(d, d, kv, hd),
                     "wo": kernel(heads * hd, heads, hd, d)},
            "ln_attn": scale(), "ln_mlp": scale(),
            "moe": {"router": Leaf((n, d, routed), "normal",
                                   config["router_initializer_std"]),
                    "wi_gate": normal(d, held, d, width),
                    "wi_up": normal(d, held, d, width),
                    "wo": normal(width, held, width, d)},
        }

    return {
        "embed": Leaf((config["vocab_size"], d), "normal",
                      config["embedding_initializer_std"]),
        "lm_head": Leaf((config["vocab_size"], d), "normal",
                        config["initializer_range"]),
        "ln_final": {"scale": Leaf((d,), "ones")},
        "periods": {f"layer_{i}": layer() for i in range(len(kinds))},
    }


def aux_spec(config):
    """The training loop keeps nothing beside the parameters: the
    router is balanced by a term of the loss."""
    return None


def routing(config, einsum, x, router):
    """(weights, idx, probs) of the tokens ``x`` (T, d): both (T,
    experts per token) over ALL the experts the router scores, and the
    softmax over all of them (T, E)."""
    logits = einsum("sd,de->se", x, router)
    picked, idx = jax.lax.top_k(logits,
                                config["moe_num_active_primary_experts"])
    return jax.nn.softmax(picked, axis=-1), idx, \
        jax.nn.softmax(logits, axis=-1)


def held_weights(w, idx, first_expert, held):
    """(T, held): the weight the router gave each held expert for each
    token, zero where it was not chosen."""
    chosen = idx[:, :, None] == first_expert + jnp.arange(held)[None, None]
    return jnp.sum(jnp.where(chosen, w[:, :, None], 0.0), axis=1)


def routed_experts(einsum, m, p, weight):
    """The held experts' part of ``sum_j w_j ReGLU_{idx_j}(m)``: each
    held expert on every token of ``m``, times ``weight`` (T, held)."""
    def one_expert(total, expert):
        gate, up, down, w_e = expert
        hidden = jax.nn.relu(einsum("sd,df->sf", m, gate)) \
            * einsum("sd,df->sf", m, up)
        return total + einsum("sf,fd->sd", hidden, down) * w_e[:, None], None

    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (p["wi_gate"], p["wi_up"], p["wo"], weight.T))
    return total


def balance_loss(counts, prob_sums, tokens):
    """``E sum_e f_e P_e`` from the assignments by expert (E,) and the
    sums over the tokens of the softmax over all experts (E,)."""
    share = jax.lax.stop_gradient(counts.astype(jnp.float32))
    share = share / jnp.sum(share)
    return counts.shape[0] * jnp.sum(share * prob_sums / tokens)


def batch_loss(config, einsum, params, batch):
    """``(loss, seen)`` of a batch of token rows (R, S): the mean
    next-token cross-entropy plus ``router_aux_loss_coef`` x the mean of
    the layers' balance losses; ``seen`` = ``{"cross_entropy", "aux_loss"
    {layer: (repeats,)}, "counts" {layer: tokens by expert (repeats,
    E)}}``."""
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    seq, tokens = batch.shape[1], batch.size
    block = math.gcd(seq, 512)
    first_expert = config["deployment"]["first_expert_held"]
    held = config["moe_num_primary_experts"]
    if config["tie_word_embeddings"]:
        raise NotImplementedError("a tied output head")
    if not (config["moe_primary_router_apply_softmax"]
            and config["norm_topk_prob"]):
        raise NotImplementedError("a router that is not a softmax over "
                                  "the selected logits")

    def blocks(x):
        return x.reshape((-1, block) + x.shape[2:])

    @jax.checkpoint
    def route_block(x, router):
        w, idx, probs = routing(config, einsum, x, router)
        counts = jnp.sum(idx[:, :, None] == jnp.arange(router.shape[-1]),
                         axis=(0, 1))
        return held_weights(w, idx, first_expert, held), counts, \
            jnp.sum(probs, axis=0)

    def attention_row(x, p, windowed, rotary):
        a = _rms_norm(x, p["ln_attn"]["scale"], eps)
        q = einsum("sd,dhe->she", a, p["attn"]["wq"]["kernel"])
        k = einsum("sd,dhe->she", a, p["attn"]["wk"]["kernel"])
        v = einsum("sd,dhe->she", a, p["attn"]["wv"]["kernel"])
        if rotary:
            q, k = _rope(q, theta), _rope(k, theta)
        o = _attention(einsum, q, k, v,
                       config["sliding_window_size"] if windowed else seq,
                       block)
        return x + einsum("she,hed->sd", o, p["attn"]["wo"]["kernel"])

    @jax.checkpoint
    def experts_block(x, weight, p):
        m = _rms_norm(x, p["ln_mlp"]["scale"], eps)
        return x + routed_experts(einsum, m, p["moe"], weight)

    kinds, _ = _period(config)
    attention = {kind: jax.checkpoint(
        lambda row, p, kind=kind: attention_row(row, p, *kind))
        for kind in set(kinds)}

    def period(x, layers):
        aux, counts = {}, {}
        for i, kind in enumerate(kinds):
            p = layers[f"layer_{i}"]
            # the routing of every token from the layer's input
            weight, by_block, prob_sums = jax.lax.map(
                lambda xb: route_block(xb, p["moe"]["router"]), blocks(x))
            counts[f"layer_{i}"] = jnp.sum(by_block, axis=0)
            aux[f"layer_{i}"] = balance_loss(
                counts[f"layer_{i}"], jnp.sum(prob_sums, axis=0), tokens)
            x = jax.lax.map(lambda row: attention[kind](row, p), x)
            x = jax.lax.map(lambda args: experts_block(*args, p),
                            (blocks(x), weight)).reshape(x.shape)
        return x, (aux, counts)

    @jax.checkpoint
    def head_block(x, targets, weight, scale, head):
        x = _rms_norm(x, scale, eps)
        logp = jax.nn.log_softmax(einsum("sd,vd->sv", x, head))
        picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
        return -jnp.sum(picked * weight)

    x, (aux, counts) = jax.lax.scan(period, params["embed"][batch],
                                    params["periods"])
    # position t is scored on token t + 1; a row's last has no target
    targets = jnp.roll(batch, -1, axis=1)
    weight = jnp.ones(batch.shape).at[:, -1].set(0.0)
    cross_entropy = jnp.sum(jax.lax.map(
        lambda args: head_block(*args, params["ln_final"]["scale"],
                                params["lm_head"]),
        (blocks(x), blocks(targets), blocks(weight)))) / jnp.sum(weight)
    mean_aux = jnp.mean(jnp.stack([jnp.mean(v) for v in aux.values()]))
    return cross_entropy + config["router_aux_loss_coef"] * mean_aux, {
        "cross_entropy": cross_entropy, "aux_loss": aux, "counts": counts}


def follow(config, workload, key, batch, steps, mode="float32"):
    """The first ``steps`` steps of training on the fixed ``batch``
    (rows, S), from the weights of ``key``.  Returns what the
    ``lm_train`` reference's ``follow`` returns: ``{"losses": [steps],
    "grad_norms": {leaf: norm of the first gradient}, "delta_norms":
    {leaf: norm of the parameters' change over the steps}}``, and with
    ``check_loss_after`` one more loss, after the last step; and
    ``"seen"``, what ``batch_loss`` saw at each step (its parts of the
    loss, the tokens by expert).

    One compiled program gives every step its loss and gradient (as the
    ``afmoe_train`` reference's: it is the compile that costs); AdamW's
    update from the gradients' history, elementwise, is a small program
    of its own for each length of the history."""
    einsum, _ = precision.products(mode)
    opt = workload["optimizer"]
    if opt["name"] != "adamw":
        raise NotImplementedError(f"optimizer {opt['name']!r}")
    spec = param_spec(config)
    loss_and_grad = jax.jit(jax.value_and_grad(
        lambda p, b: batch_loss(config, einsum, p, b), has_aux=True))
    update = jax.jit(lambda p, history: _adamw_update(opt, p, history),
                     donate_argnums=0)
    norms = jax.jit(weights.leaf_norms)
    params = jax.jit(lambda k: weights.make(k, spec))(key)
    history, found = (), {"losses": [], "seen": []}
    for _ in range(steps):
        (loss, seen), grads = loss_and_grad(params, batch)
        found["losses"].append(float(loss))
        found["seen"].append(jax.device_get(seen))
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
            lambda p, b: batch_loss(config, einsum, p, b)[0])(params,
                                                             batch)))
    return found
