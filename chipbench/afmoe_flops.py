"""Operations of the ``afmoe_train`` configurations (Trinity-Mini), from
shapes alone, in ``flops.py``'s convention: forward and backward, a
multiply-add as two, no credit for recomputation.  What is counted is
what THIS CHIP computes: the experts it holds under a balanced router,
its slice of the vocabulary."""

from chipbench import flops

# one SwiGLU of hidden d and width f: gate, up and down
_SWIGLU_MATRICES = 3
# forward 2 FLOPs a multiply-add, backward twice the forward
_TRAIN_FLOPS_PER_WEIGHT = 6


def routed_width(config):
    """How many experts the router scores: the published count (the
    configuration's own ``num_experts`` is what this chip holds)."""
    return config["published"]["num_experts"]


def attention_matmul_params(config):
    """wq, wk, wv, the output gate and wo of one layer."""
    d, hd = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return d * hd * (2 * heads + 2 * kv) + heads * hd * d


def held_assignments_per_token(config):
    """Under a balanced router, the assignments a token sends to the
    experts held here, a layer."""
    return config["num_experts_per_tok"] * config["num_experts"] \
        / routed_width(config)


def expert_matmul_params(config):
    """One expert (routed or shared): 3 x hidden x width."""
    return _SWIGLU_MATRICES * config["hidden_size"] \
        * config["moe_intermediate_size"]


def matmul_params_per_token(config):
    """Parameters that are matrices a token is multiplied by here: every
    layer's attention, the dense layers' SwiGLU, and of each expert
    layer the router, the shared experts and as many routed experts as
    a balanced router sends a token to on this chip; the head."""
    d = config["hidden_size"]
    layers, dense = config["num_hidden_layers"], config["num_dense_layers"]
    expert_layer = d * routed_width(config) + expert_matmul_params(config) * (
        config["num_shared_experts"] + held_assignments_per_token(config))
    return layers * attention_matmul_params(config) \
        + dense * _SWIGLU_MATRICES * d * config["intermediate_size"] \
        + (layers - dense) * expert_layer + config["vocab_size"] * d


def keys_attended(config, seq_len):
    """Per layer, the mean number of keys its mask lets a query see."""
    return [flops.mean_keys_attended(
        seq_len, config["sliding_window"]
        if kind == "sliding_attention" else None)
        for kind in config["layer_types"]]


def attention_train_flops_per_token(config, seq_len):
    """QK^T and PV of every layer, forward (2 products) and backward
    (4), over the keys each layer's mask lets a query see."""
    heads, hd = config["num_attention_heads"], config["head_dim"]
    return 6 * 2 * heads * hd * sum(keys_attended(config, seq_len))


def train_flops_per_token(config, seq_len):
    return _TRAIN_FLOPS_PER_WEIGHT * matmul_params_per_token(config) \
        + attention_train_flops_per_token(config, seq_len)


def grouped_products_train_flops_per_assignment(config):
    """The three grouped products of one assignment (a token on one
    routed expert), forward and backward: 18 x hidden x width."""
    return _TRAIN_FLOPS_PER_WEIGHT * expert_matmul_params(config)
