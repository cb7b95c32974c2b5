"""Operations and bytes of the ``kimi_linear_train`` configurations
(Kimi-Linear: delta-rule layers and latent-attention layers in one
model, routed experts), from shapes alone, in ``flops.py``'s convention:
forward and backward, a multiply-add as two, no credit for
recomputation.  What is counted is what THIS CHIP computes at the
PUBLISHED widths: the experts it holds under a balanced router, its
slice of the vocabulary, latent attention over the causal triangle at
192-wide keys and 128-wide values with no padding.

The delta rule is counted by its RECURRENCE, which no chunk length
enters: a token and a head, ``S^T k`` (the read that corrects), ``k
u^T`` (the write) and ``S^T q`` (the output), each d_k x d_v
multiply-adds.  The chunked form trades the state's reads and writes
for products inside a chunk and does more arithmetic at any chunk over
a few positions; it is held to this count all the same
(``kda_scan_roofline``), as a later kernel will be.  Elementwise work
(the convolutions excepted: K multiply-adds a channel), decays, norms
and gates count nothing."""

from chipbench import flops

# forward and backward: the backward twice the forward
_TRAIN = 3
_SWIGLU_MATRICES = 3
_ACTIVATION_BYTES = 2     # bfloat16: q, k, v, o and their gradients
_GATE_BYTES = 4           # float32: g and beta


def kda_sizes(config):
    """(heads, a head's width, heads x width, convolution taps)."""
    linear = config["linear_attn_config"]
    heads, width = linear["num_heads"], linear["head_dim"]
    return heads, width, heads * width, linear["short_conv_kernel_size"]


def routed_width(config):
    """How many experts the router scores: the published count (the
    configuration's own ``num_experts`` is what this chip holds)."""
    return config["published"]["num_experts"]


def held_assignments_per_token(config):
    """Under a balanced router, the assignments a token sends to the
    experts held here, a layer."""
    return config["num_experts_per_token"] * config["num_experts"] \
        / routed_width(config)


def rule_flops_per_token(config):
    """Forward FLOPs a token of one kda layer's delta rule."""
    heads, width, _, _ = kda_sizes(config)
    return 3 * 2 * heads * width * width


def rule_train_flops_per_token(config):
    return _TRAIN * rule_flops_per_token(config)


def rule_train_bytes_per_token(config):
    """Bytes of HBM a token that no implementation of the rule can
    avoid, forward and backward: forward it reads q, k, v (activation
    dtype), g and beta (float32) and writes o; backward it reads them
    again with o's gradient and writes theirs."""
    heads, _, inner, _ = kda_sizes(config)
    inputs = 3 * inner * _ACTIVATION_BYTES + (inner + heads) * _GATE_BYTES
    output = inner * _ACTIVATION_BYTES
    return (inputs + output) + (inputs + output + inputs)


def kda_layer_flops_per_token(config):
    """Forward FLOPs a token of one kda layer's mixer: the projections
    of the input (q, k, v, the two low-rank gates, beta), three
    convolutions, the rule, the output projection."""
    heads, width, inner, taps = kda_sizes(config)
    d = config["hidden_size"]
    projections = d * (3 * inner + 2 * width + heads) + 2 * width * inner
    return 2 * projections + 3 * 2 * taps * inner \
        + rule_flops_per_token(config) + 2 * inner * d


def mla_projection_flops_per_token(config):
    """Forward FLOPs a token of one mla layer's wq, kv_a, kv_b and wo."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    rank, nope = config["kv_lora_rank"], config["qk_nope_head_dim"]
    shared, value = config["qk_rope_head_dim"], config["v_head_dim"]
    return 2 * (d * heads * (nope + shared) + d * (rank + shared)
                + rank * heads * (nope + value) + heads * value * d)


def attention_train_flops_per_token(config, seq_len):
    """QK^T (192 wide) and PV (128 wide) of every mla layer, forward (2
    products) and backward (4), over the keys a causal query sees."""
    heads = config["num_attention_heads"]
    wide = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    layers = sum(kind == "mla" for kind in config["layer_types"])
    return layers * _TRAIN * 2 * heads * (wide + config["v_head_dim"]) \
        * flops.mean_keys_attended(seq_len, None)


def expert_matmul_params(config):
    """One expert (routed or shared): 3 x hidden x width."""
    return _SWIGLU_MATRICES * config["hidden_size"] \
        * config["moe_intermediate_size"]


def grouped_products_train_flops_per_assignment(config):
    """The three grouped products of one assignment (a token on one
    routed expert), forward and backward: 18 x hidden x width."""
    return _TRAIN * 2 * expert_matmul_params(config)


def train_flops_per_token(config, seq_len):
    d = config["hidden_size"]
    kinds, dense = config["layer_types"], config["first_k_dense_replace"]
    kda = sum(kind == "kda" for kind in kinds)
    expert_layer = 2 * (d * routed_width(config)
                        + expert_matmul_params(config) * (
                            config["num_shared_experts"]
                            + held_assignments_per_token(config)))
    forward = kda * kda_layer_flops_per_token(config) \
        + (len(kinds) - kda) * mla_projection_flops_per_token(config) \
        + dense * 2 * _SWIGLU_MATRICES * d * config["intermediate_size"] \
        + (len(kinds) - dense) * expert_layer \
        + 2 * config["vocab_size"] * d
    return _TRAIN * forward + attention_train_flops_per_token(config,
                                                              seq_len)
