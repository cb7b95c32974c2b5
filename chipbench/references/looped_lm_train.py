"""Plain float32 reference of the ``looped_lm_train`` adapter: Ouro's
looped language model (``modeling_ouro.py`` beside the published
``config.json``; the objective is section 3 of arXiv:2510.25741) trained
with AdamW.  ONE stack of ``N`` layers runs ``R = total_ut_steps`` times
over the same weights; the final norm follows every pass and its output
is both that pass's exit and the next pass's input; a gate on every
exit gives each token a distribution over the passes to leave at, and
the loss is the expected cross-entropy under it less an entropy bonus.

With ``norm(x; s) = x / sqrt(mean(x^2) + eps) * s``::

    layer_l(x): a = Attn_l(norm(x; s1_l));  x = x + norm(a; s2_l)
                f = Wd_l (silu(Wg_l m) * (Wu_l m)), m = norm(x; s3_l)
                x = x + norm(f; s4_l)
    Attn_l(u):  q, k, v = u Wq_l, u Wk_l, u Wv_l in heads; rotary
                positions on q and k; causal softmax(q k^T / sqrt(D)) v;
                heads joined; Wo_l
    h_0 = E[ids]
    for t = 1..R:  z = h_{t-1};  for l = 1..N: z = layer_l(z)
                   h_t = norm(z; s_final)
                   g_t = h_t . wg + bg;   lambda_t = sigmoid(g_t)
                   nll_t = logsumexp(h_t H^T) - (h_t H^T)[target]
    p_t = lambda_t prod_{j<t} (1 - lambda_j)  (t < R);
    p_R = prod_{j<R} (1 - lambda_j)
    loss = mean over the scored tokens of
           sum_t p_t nll_t - beta * (- sum_t p_t log p_t)

Straightforward ``jax.numpy``: a loop over the passes around a Python
loop over the layers (the program has them the other way round: a
Python loop over the passes around a scan over the layers), no kernel,
no bfloat16, no fused loss, nothing imported from the program.  Attention takes the rows one after another,
everything else blocks of tokens, and every layer application is
checkpointed, so that one compiled loss-and-gradient program fits,
alone, on one chip beside its weights.  The pieces that do not depend on
the architecture (the norm, the rotary positions, the masked attention
in blocks, AdamW from the gradients' history) are the ``lm_train``
reference's.
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
    """Seeded weights know nothing, so every pass's cross-entropy is
    ln(vocab) and half the variance of the logits (a normalised hidden
    state against rows of the head, std ``initializer_range``), and so
    is their expectation; the entropy bonus takes at most
    ``exit_entropy_coeff`` x ln(passes) off it (0.07 for four)."""
    return math.log(config["vocab_size"]) \
        + config["hidden_size"] * config["initializer_range"] ** 2 / 2


def param_spec(config):
    d, hd = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    ff, n = config["intermediate_size"], config["num_hidden_layers"]
    if set(config["layer_types"]) != {"full_attention"} \
            or len(config["layer_types"]) != n:
        raise NotImplementedError("layers that are not full_attention")

    def kernel(fan_in, *shape):
        return {"kernel": Leaf((n,) + shape, "normal",
                               1.0 / math.sqrt(fan_in))}

    def scale(kind="ones", std=1.0):
        return {"scale": Leaf((n, d), kind, std)}

    # the norms AFTER attention and feed-forward start small (drawn
    # about zero, rms 0.1), as ``afmoe_train``'s: the configuration's
    # ``assumed`` says why
    after = ("normal", 0.1)
    return {
        "embed": Leaf((config["vocab_size"], d), "normal",
                      config["initializer_range"]),
        "lm_head": Leaf((config["vocab_size"], d), "normal",
                        config["initializer_range"]),
        "early_exit_gate": {
            "kernel": Leaf((d, 1), "normal", 1.0 / math.sqrt(d)),
            "bias": Leaf((1,), "zeros")},
        # the program's tree: the loop's one period of one layer kind,
        # its parameters stacked over the depth
        "loop": {
            "ln_final": {"scale": Leaf((d,), "ones")},
            "periods": {"layer_0": {
                "attn": {"wq": kernel(d, d, heads, hd),
                         "wk": kernel(d, d, kv, hd),
                         "wv": kernel(d, d, kv, hd),
                         "wo": kernel(heads * hd, heads, hd, d)},
                "ln_attn": scale(), "ln_post_attn": scale(*after),
                "ln_mlp": scale(), "ln_post_mlp": scale(*after),
                "mlp": {"wi_gate": kernel(d, d, ff),
                        "wi_up": kernel(d, d, ff),
                        "wo": kernel(ff, ff, d)},
            }},
        },
    }


def aux_spec(config):
    return None


def exit_distribution(gates):
    """``gates`` (R, ...) -> (p, log p), each (R, ...): the chance of
    leaving at pass t is the gate's there times the chance of having
    stayed at every pass before; the last pass takes what is left."""
    lam = jax.nn.sigmoid(gates)
    p, log_p, stayed, log_stayed = [], [], 1.0, 0.0
    for t in range(gates.shape[0] - 1):
        p.append(lam[t] * stayed)
        log_p.append(jax.nn.log_sigmoid(gates[t]) + log_stayed)
        stayed = stayed * (1.0 - lam[t])
        log_stayed = log_stayed + jax.nn.log_sigmoid(-gates[t])
    return jnp.stack(p + [stayed * jnp.ones_like(lam[0])]), \
        jnp.stack(log_p + [log_stayed * jnp.ones_like(lam[0])])


def batch_loss(config, einsum, params, batch, with_exits=False):
    """The objective above on a batch of token rows (B, S); with
    ``with_exits`` also ``p`` (R, B, S)."""
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    passes, beta = config["total_ut_steps"], config["exit_entropy_coeff"]
    rows, seq = batch.shape
    block = math.gcd(seq, 512)
    if config["tie_word_embeddings"]:
        raise NotImplementedError("a tied output head")

    def blocks(x):
        return x.reshape((-1, block) + x.shape[2:])

    def attention_row(x, p):
        a = _rms_norm(x, p["ln_attn"]["scale"], eps)
        q = _rope(einsum("sd,dhe->she", a, p["attn"]["wq"]["kernel"]), theta)
        k = _rope(einsum("sd,dhe->she", a, p["attn"]["wk"]["kernel"]), theta)
        v = einsum("sd,dhe->she", a, p["attn"]["wv"]["kernel"])
        o = _attention(einsum, q, k, v, seq, block)
        out = einsum("she,hed->sd", o, p["attn"]["wo"]["kernel"])
        return x + _rms_norm(out, p["ln_post_attn"]["scale"], eps)

    def feed_forward_block(x, p):
        m = _rms_norm(x, p["ln_mlp"]["scale"], eps)
        gate = jax.nn.silu(einsum("sd,df->sf", m,
                                  p["mlp"]["wi_gate"]["kernel"]))
        up = einsum("sd,df->sf", m, p["mlp"]["wi_up"]["kernel"])
        out = einsum("sf,fd->sd", gate * up, p["mlp"]["wo"]["kernel"])
        return x + _rms_norm(out, p["ln_post_mlp"]["scale"], eps)

    @jax.checkpoint
    def layer(x, p):
        x = jax.lax.map(lambda row: attention_row(row, p), x)
        return jax.lax.map(lambda xb: feed_forward_block(xb, p),
                           blocks(x)).reshape(x.shape)

    @jax.checkpoint
    def exit_block(h, targets, head, gate):
        """One block of one pass's exit: every token's cross-entropy
        and the gate's logit."""
        logp = jax.nn.log_softmax(einsum("sd,vd->sv", h, head))
        nll = -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
        return nll, einsum("sd,do->so", h, gate["kernel"])[:, 0] \
            + gate["bias"][0]

    # each layer's own weights, taken from the stack ONCE (one split a
    # leaf): a layer's gradient is then the sum of its four passes'
    # and the stack's one concatenation of the layers'
    stack = params["loop"]["periods"]["layer_0"]
    depth = stack["ln_attn"]["scale"].shape[0]
    parts = jax.tree.map(lambda a: jnp.split(a, depth), stack)
    layers = [jax.tree.map(lambda leaf: leaf[l][0], parts,
                           is_leaf=lambda x: isinstance(x, list))
              for l in range(depth)]
    # position t is scored on token t + 1; a row's last has no target
    targets = jnp.roll(batch, -1, axis=1)
    weight = jnp.ones(batch.shape).at[:, -1].set(0.0)
    def one_pass(h, _):
        """A pass: every layer with its own weights, the SAME in every
        pass, the final norm, and that pass's exit."""
        for p_l in layers:
            h = layer(h, p_l)
        h = _rms_norm(h, params["loop"]["ln_final"]["scale"], eps)
        nll, gate = jax.lax.map(
            lambda args: exit_block(*args, params["lm_head"],
                                    params["early_exit_gate"]),
            (blocks(h), blocks(targets)))
        return h, (nll.reshape(rows, seq), gate.reshape(rows, seq))

    # the passes are a loop of the compiler's, not of Python: its
    # backward pass then adds each pass's weight gradients to ONE running
    # sum (four passes' worth side by side, 32 x 205 MB, do not fit)
    _, (nll, gates) = jax.lax.scan(one_pass, params["embed"][batch], None,
                                   length=passes)
    p, log_p = exit_distribution(gates)
    per_token = jnp.sum(p * nll, axis=0) \
        - beta * -jnp.sum(p * log_p, axis=0)
    loss = jnp.sum(per_token * weight) / jnp.sum(weight)
    return (loss, p) if with_exits else loss


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
