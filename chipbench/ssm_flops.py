"""Operations and bytes of the ``granite_hybrid_train`` configurations
(Granite-4.0-H: Mamba-2 layers and attention layers in one model), from
shapes alone, in ``flops.py``'s convention: forward and backward, a
multiply-add as two, no credit for recomputation.

A mamba layer's scan is counted as its chunked form's four products at
a given chunk length Q, the two inside a chunk over the causal triangle
only (position l reads the (Q + 1) / 2 sources s <= l of its chunk that
a mean position has): ``C B^T``, its masked product with ``dt x``, the
chunk's contribution to the state ``B^T (dt x)`` and the read of the
carried state ``C S``.  ``train_flops_per_token`` (``mfu_pct``) takes
the published ``mamba_chunk_size``; ``ssm_scan_roofline`` the chunk
length the program's own counters say it ran.  Elementwise work (the
convolution excepted: K multiply-adds a channel), decays, norms and
gates count nothing."""

from chipbench import flops

# forward and backward: the backward twice the forward
_TRAIN = 3
_ACTIVATION_BYTES = 2     # bfloat16: x, B, C, y and their gradients
_DT_BYTES = 4             # float32


def mamba_sizes(config):
    """(heads, d_head, groups, d_state, d_inner, convolved channels)."""
    heads, width = config["mamba_n_heads"], config["mamba_d_head"]
    groups, state = config["mamba_n_groups"], config["mamba_d_state"]
    inner = heads * width
    return heads, width, groups, state, inner, inner + 2 * groups * state


def scan_flops_per_token(config, chunk):
    """Forward FLOPs a token of one mamba layer's scan in chunks of
    ``chunk`` positions."""
    _, _, groups, state, inner, _ = mamba_sizes(config)
    sources = (chunk + 1) / 2           # of a mean position's chunk
    within = 2 * groups * state * sources + 2 * inner * sources
    across = 2 * state * inner + 2 * state * inner
    return within + across


def scan_train_flops_per_token(config, chunk):
    return _TRAIN * scan_flops_per_token(config, chunk)


def scan_train_bytes_per_token(config):
    """Bytes of HBM a token that no scan can avoid, forward and
    backward: forward it reads x, B, C (activation dtype) and dt
    (float32) and writes y; backward it reads them again with y's
    gradient and writes theirs."""
    heads, _, groups, state, inner, _ = mamba_sizes(config)
    inputs = (inner + 2 * groups * state) * _ACTIVATION_BYTES \
        + heads * _DT_BYTES
    output = inner * _ACTIVATION_BYTES
    return (inputs + output) + (inputs + output + inputs)


def mamba_layer_flops_per_token(config):
    """Forward FLOPs a token of one mamba layer's mixer: in-projection,
    convolution, scan at the published chunk, out-projection."""
    heads, _, _, _, inner, conv = mamba_sizes(config)
    d = config["hidden_size"]
    return 2 * d * (inner + conv + heads) \
        + 2 * config["mamba_d_conv"] * conv \
        + scan_flops_per_token(config, config["mamba_chunk_size"]) \
        + 2 * inner * d


def attention_projection_flops_per_token(config):
    """Forward FLOPs a token of wq, wk, wv and wo of one attention
    layer."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    hd = d // heads
    return 2 * d * hd * (heads + 2 * config["num_key_value_heads"]) \
        + 2 * heads * hd * d


def attention_train_flops_per_token(config, seq_len):
    """QK^T and PV of every attention layer, forward (2 products) and
    backward (4), over the keys a causal query sees."""
    d = config["hidden_size"]
    layers = sum(kind == "attention" for kind in config["layer_types"])
    return layers * _TRAIN * 2 * 2 * d \
        * flops.mean_keys_attended(seq_len, None)


def train_flops_per_token(config, seq_len):
    d = config["hidden_size"]
    kinds = config["layer_types"]
    mamba = sum(kind == "mamba" for kind in kinds)
    mlp = 3 * 2 * d * config["shared_intermediate_size"]
    forward = mamba * mamba_layer_flops_per_token(config) \
        + (len(kinds) - mamba) * attention_projection_flops_per_token(config) \
        + len(kinds) * mlp + 2 * config["vocab_size"] * d
    return _TRAIN * forward + attention_train_flops_per_token(config,
                                                              seq_len)
