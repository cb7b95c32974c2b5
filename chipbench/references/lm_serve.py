"""Plain float32 reference of the ``lm_serve`` adapter: the full causal
forward pass of a decoder-only language model (pre-RMSNorm, rotary
positions, grouped-query attention under a causal sliding window,
SwiGLU, the output head tied to the embedding) over one whole sequence
at a time.  No cache, no batch, no kernel, nothing imported from the
program.

It makes its own weights from the key, one layer at a time
(``chipbench/served_weights.py``: the values the configuration serves,
rounded to the served type, held in float32), carries every sequence
through that layer, and goes on to the next: one layer's float32
weights are all it holds, so it fits beside nothing and after anything.
It returns, for each sequence, every layer's keys (roped, as a cache
holds them) and values at every position, and the logits at the
positions asked for.

``mode="fp8"`` is the control (``references/precision.py``): every
product takes its operands rounded to e4m3 under a per-tensor scale.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import served_weights
from chipbench.references import precision
from chipbench.weights import Leaf

BLOCK = 512         # rows of queries, and of the MLP, at a time


def top_spec(config):
    d = config["hidden_size"]
    return {"embed": Leaf((config["vocab_size"], d), "normal",
                          config["initializer_range"]),
            "ln_final": {"scale": Leaf((d,), "ones")}}


def layer_spec(config):
    d = config["hidden_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, ff = config["head_dim"], config["intermediate_size"]

    def kernel(fan_in, *shape):
        return {"kernel": Leaf(shape, "normal", 1.0 / math.sqrt(fan_in))}

    return {"attn": {"wq": kernel(d, d, heads, hd),
                     "wk": kernel(d, d, kv, hd),
                     "wv": kernel(d, d, kv, hd),
                     "wo": kernel(heads * hd, heads, hd, d)},
            "ln_attn": {"scale": Leaf((d,), "ones")},
            "ln_mlp": {"scale": Leaf((d,), "ones")},
            "mlp": {"wi_gate": kernel(d, d, ff),
                    "wi_up": kernel(d, d, ff),
                    "wo": kernel(ff, ff, d)}}


def param_spec(config):
    """The whole tree as the program holds it: the layers' leaves
    stacked on a leading axis."""
    layers = config["num_hidden_layers"]
    stacked = jax.tree.map(
        lambda leaf: Leaf((layers,) + tuple(leaf.shape), leaf.kind,
                          leaf.std),
        layer_spec(config), is_leaf=lambda x: isinstance(x, Leaf))
    return {**top_spec(config), "layers": stacked}


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
    h // (H / KV); position t sees positions (t - window, t].  In blocks
    of query rows, so one block's scores are all that lives."""
    seq, heads, hd = q.shape
    kv = k.shape[1]
    qg = q.reshape(seq // block, block, kv, heads // kv, hd)
    k_pos = jnp.arange(seq)[None, :]

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


def layer_forward(config, einsum, x, p):
    """One layer over one sequence x (S, d), S a multiple of the block:
    (x, keys as cached (S, KV, D), values)."""
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    seq = x.shape[0]
    block = math.gcd(seq, BLOCK)
    window = config["sliding_window"] or seq
    h = _rms_norm(x, p["ln_attn"]["scale"], eps)
    q = _rope(einsum("sd,dhe->she", h, p["attn"]["wq"]["kernel"]), theta)
    k = _rope(einsum("sd,dhe->she", h, p["attn"]["wk"]["kernel"]), theta)
    v = einsum("sd,dhe->she", h, p["attn"]["wv"]["kernel"])
    o = _attention(einsum, q, k, v, window, block)
    x = x + einsum("she,hed->sd", o, p["attn"]["wo"]["kernel"])

    def mlp_block(xb):
        h = _rms_norm(xb, p["ln_mlp"]["scale"], eps)
        gate = jax.nn.silu(einsum("sd,df->sf", h,
                                  p["mlp"]["wi_gate"]["kernel"]))
        up = einsum("sd,df->sf", h, p["mlp"]["wi_up"]["kernel"])
        return xb + einsum("sf,fd->sd", gate * up, p["mlp"]["wo"]["kernel"])

    x = jax.lax.map(mlp_block, x.reshape(seq // block, block, -1))
    return x.reshape(seq, -1), k, v


def padded(length, multiple=BLOCK):
    return -(-length // multiple) * multiple


def forward(config, key, sequences, mode="float32", keep_cache=True):
    """The reference over ``sequences`` = [{"tokens": ids, "read": the
    positions whose logits are wanted}].  Causal, so a sequence is
    padded at its end to a multiple of the block (a few shapes to
    compile, whatever the lengths) and the padding changes nothing
    before it.

    Returns [{"k", "v": (layers, length, KV, D) float32 on the host, if
    ``keep_cache``; "logits": (len(read), vocab)}]."""
    if not config["tie_word_embeddings"]:
        raise NotImplementedError("an untied output head")
    einsum, _ = precision.products(mode)
    dtype = served_weights.dtype_of(config)
    layers = config["num_hidden_layers"]
    spec = layer_spec(config)

    make_layer = jax.jit(lambda k, layer: jax.tree.map(
        lambda a: a.astype(jnp.float32),
        served_weights.make_layer(k, spec, layer, dtype)))
    top = jax.jit(lambda k: jax.tree.map(
        lambda a: a.astype(jnp.float32),
        served_weights.make_top(k, top_spec(config), dtype)))(key)
    one_layer = jax.jit(lambda x, p: layer_forward(config, einsum, x, p),
                        donate_argnums=0)

    lengths = [len(s["tokens"]) for s in sequences]
    xs = []
    for s, n in zip(sequences, lengths):
        ids = np.zeros(padded(n), np.int32)
        ids[:n] = np.asarray(s["tokens"], np.int32)
        xs.append(top["embed"][jnp.asarray(ids)])
    out = [{"k": [], "v": []} for _ in sequences]
    for layer in range(layers):
        p = make_layer(key, layer)
        for i, n in enumerate(lengths):
            xs[i], k, v = one_layer(xs[i], p)
            if keep_cache:
                out[i]["k"].append(np.asarray(k[:n]))
                out[i]["v"].append(np.asarray(v[:n]))
        del p

    @jax.jit
    def head(x, rows, scale, embed):
        x = _rms_norm(x[rows], scale, config["rms_norm_eps"])
        return einsum("sd,vd->sv", x, embed)

    for i, s in enumerate(sequences):
        rows = np.asarray(s["read"], np.int32)
        fixed = np.zeros(padded(len(rows), 64), np.int32)
        fixed[:len(rows)] = rows
        out[i]["logits"] = np.asarray(head(
            xs[i], jnp.asarray(fixed), top["ln_final"]["scale"],
            top["embed"]))[:len(rows)]
        if keep_cache:
            out[i]["k"] = np.stack(out[i]["k"])
            out[i]["v"] = np.stack(out[i]["v"])
        else:
            del out[i]["k"], out[i]["v"]
    return out
