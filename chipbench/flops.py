"""Operations the algorithms need, from shapes alone.  Model FLOPs in
the MFU convention: forward and backward, a multiply-add as two, and
no credit for recomputation."""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    """Published peaks of ``device_kind``; a device that is not in
    peaks.json is an error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    try:
        return table[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}: add it "
            f"to chipbench/peaks.json with its source") from None


def mean_keys_attended(seq_len, window):
    """Mean over the positions of a sequence of how many keys a causal
    query sees: position i (from 0) sees min(i + 1, window)."""
    window = min(window or seq_len, seq_len)
    ramp = window * (window + 1) // 2            # positions 0..window-1
    return (ramp + (seq_len - window) * window) / seq_len


def lm_attention_train_flops_per_token(config, seq_len):
    """QK^T and PV of every layer, forward (2 products) and backward
    (4), over the keys the causal window lets a query see."""
    heads, hd = config["num_attention_heads"], config["head_dim"]
    keys = mean_keys_attended(seq_len, config["sliding_window"])
    return config["num_hidden_layers"] * 6 * 2 * heads * hd * keys


def lm_matmul_params(config):
    """Parameters that are matrices a token is multiplied by, the
    logits projection among them (tied or not, it is one product)."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    kv, hd = config["num_key_value_heads"], config["head_dim"]
    layer = d * heads * hd + 2 * d * kv * hd + heads * hd * d \
        + 3 * d * config["intermediate_size"]
    return config["num_hidden_layers"] * layer + config["vocab_size"] * d


def lm_train_flops_per_token(config, seq_len):
    return 6 * lm_matmul_params(config) \
        + lm_attention_train_flops_per_token(config, seq_len)


def resnet_conv_layers(config):
    """(kernel_h, kernel_w, c_in, c_out, out_h, out_w) of every
    convolution of a bottleneck ResNet v1.5 and (c_in, c_out) of its
    classifier, from the configuration's shapes."""
    size, width = config["image_size"], config["num_filters"]
    convs = []
    size = size // 2                                    # 7x7 stride 2
    convs.append((7, 7, config["num_channels"], width, size, size))
    size = size // 2                                    # 3x3 max pool
    c_in = width
    for stage, blocks in enumerate(config["stage_sizes"]):
        mid = width * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            out = size // stride
            convs.append((1, 1, c_in, mid, size, size))
            convs.append((3, 3, mid, mid, out, out))    # v1.5: stride here
            convs.append((1, 1, mid, 4 * mid, out, out))
            if c_in != 4 * mid or stride != 1:
                convs.append((1, 1, c_in, 4 * mid, out, out))
            c_in, size = 4 * mid, out
    return convs, (c_in, config["num_classes"])


def resnet_train_flops_per_image(config):
    convs, (c_in, classes) = resnet_conv_layers(config)
    macs = sum(kh * kw * ci * co * oh * ow
               for kh, kw, ci, co, oh, ow in convs) + c_in * classes
    return 3 * 2 * macs
