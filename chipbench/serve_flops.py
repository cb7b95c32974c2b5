"""What a served decoder-only LM needs for the tokens of its requests,
from the requests alone: forward FLOPs (a multiply-add as two) and the
bytes a decode tick cannot avoid reading.  Nothing here knows how the
program batches, pads or reads its cache: bucket padding, masked slots
and gathered block views are its own cost and are not counted.
"""


def layer_matmul_params(config):
    d, heads = config["hidden_size"], config["num_attention_heads"]
    kv, hd = config["num_key_value_heads"], config["head_dim"]
    return d * heads * hd + 2 * d * kv * hd + heads * hd * d \
        + 3 * d * config["intermediate_size"]


def head_params(config):
    """The logits product (tied or not, it is one product)."""
    return config["vocab_size"] * config["hidden_size"]


def keys_attended(position, config):
    """A token at ``position`` (from 0) sees itself and what the window
    leaves of the positions before it."""
    window = config["sliding_window"] or position + 1
    return min(position + 1, window)


def token_flops(config, position, with_head):
    """One token through every layer at ``position`` of its sequence:
    2 x the parameters it multiplies, and QK^T and PV over its keys;
    the logits product only where a token is read off (``with_head``)."""
    layers = config["num_hidden_layers"]
    attention = 4 * config["num_attention_heads"] * config["head_dim"] \
        * keys_attended(position, config)
    return layers * (2 * layer_matmul_params(config) + attention) \
        + (2 * head_params(config) if with_head else 0)


def _keys_sum(first, last, config):
    """Sum of ``keys_attended`` over positions first..last-1."""
    window = config["sliding_window"] or last

    def upto(n):            # positions 0..n-1
        ramp = min(n, window)
        return ramp * (ramp + 1) // 2 + (n - ramp) * window
    return upto(last) - upto(first)


def prefill_flops(config, prompt_tokens):
    """A prompt's forward pass; the logits at its last position only."""
    layers = config["num_hidden_layers"]
    return layers * (2 * layer_matmul_params(config) * prompt_tokens
                     + 4 * config["num_attention_heads"]
                     * config["head_dim"]
                     * _keys_sum(0, prompt_tokens, config)) \
        + 2 * head_params(config)


def decode_flops(config, position):
    """One generated token fed back at ``position``, its logits read."""
    return token_flops(config, position, True)


def weight_bytes(config, bytes_per_parameter=2):
    """What one decode tick reads of the weights, whatever its batch:
    every layer's matrices and the logits product, once."""
    return bytes_per_parameter * (
        config["num_hidden_layers"] * layer_matmul_params(config)
        + head_params(config))


def kv_row_bytes(config, bytes_per_value=2):
    """One position's keys and values over all layers."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] \
        * bytes_per_value * config["num_hidden_layers"]


def decode_kv_bytes(config, position):
    """The cached rows one decode token at ``position`` has to read."""
    return kv_row_bytes(config) * keys_attended(position, config)
