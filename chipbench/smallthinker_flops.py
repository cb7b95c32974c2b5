"""Operations of the ``smallthinker_train`` configurations
(SmallThinker), from shapes alone, in ``flops.py``'s convention: forward
and backward, a multiply-add as two, no credit for recomputation.  What
is counted is what THIS CHIP computes: the experts it holds under a
balanced router (``moe_num_active_primary_experts`` x held / routed
assignments a token a layer), its slice of the vocabulary.  The routed
experts' and the masks' parts are ``afmoe_flops``'s, which reads the
keys the configuration repeats under Trinity-Mini's names."""

from chipbench import afmoe_flops

# forward 2 FLOPs a multiply-add, backward twice the forward
_TRAIN_FLOPS_PER_WEIGHT = 6


def attention_matmul_params(config):
    """wq, wk, wv and wo of one layer (no gate)."""
    d, hd = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return d * hd * (heads + 2 * kv) + heads * hd * d


def matmul_params_per_token(config):
    """Parameters that are matrices a token is multiplied by here:
    every layer's attention and router and as many routed experts as a
    balanced router sends a token to on this chip; the head."""
    d = config["hidden_size"]
    layer = attention_matmul_params(config) \
        + d * afmoe_flops.routed_width(config) \
        + afmoe_flops.expert_matmul_params(config) \
        * afmoe_flops.held_assignments_per_token(config)
    return config["num_hidden_layers"] * layer + config["vocab_size"] * d


def attention_train_flops_per_token(config, seq_len):
    """QK^T and PV of every layer over the keys its mask lets a query
    see: ``afmoe_flops``'s count, from the layers' kinds and the window
    the configuration repeats under Trinity-Mini's names."""
    return afmoe_flops.attention_train_flops_per_token(config, seq_len)


def train_flops_per_token(config, seq_len):
    return _TRAIN_FLOPS_PER_WEIGHT * matmul_params_per_token(config) \
        + attention_train_flops_per_token(config, seq_len)
