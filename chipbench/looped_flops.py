"""Operations of the ``looped_lm_train`` configurations (Ouro), from
shapes alone, in ``flops.py``'s convention: forward and backward, a
multiply-add as two, no credit for recomputation.  A looped model's
weights are each used ``total_ut_steps`` times a token: every pass runs
the whole stack, and every pass has an exit (the head and the gate)."""

from chipbench import flops

# forward 2 FLOPs a multiply-add, backward twice the forward
_TRAIN_FLOPS_PER_WEIGHT = 6


def layer_matmul_params(config):
    """wq, wk, wv, wo and the SwiGLU's three matrices of one layer."""
    d, hd = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return d * hd * (heads + 2 * kv) + heads * hd * d \
        + 3 * d * config["intermediate_size"]


def exit_matmul_params(config):
    """One exit: the head's rows and the gate's one."""
    return (config["vocab_size"] + 1) * config["hidden_size"]


def matmul_uses_per_token(config):
    """Matrix entries a token is multiplied by in one forward pass of
    the model: the layers and the exit, each ``total_ut_steps``
    times."""
    return config["total_ut_steps"] * (
        config["num_hidden_layers"] * layer_matmul_params(config)
        + exit_matmul_params(config))


def attention_train_flops_per_token(config, seq_len):
    """QK^T and PV of every layer application, forward (2 products) and
    backward (4), over the keys a causal query sees."""
    heads, hd = config["num_attention_heads"], config["head_dim"]
    keys = flops.mean_keys_attended(seq_len, config["sliding_window"])
    return config["total_ut_steps"] * config["num_hidden_layers"] \
        * 6 * 2 * heads * hd * keys


def train_flops_per_token(config, seq_len):
    return _TRAIN_FLOPS_PER_WEIGHT * matmul_uses_per_token(config) \
        + attention_train_flops_per_token(config, seq_len)
