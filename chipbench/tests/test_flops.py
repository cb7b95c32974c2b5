"""flops.py against counts worked by hand."""

import json
import os

import pytest

from chipbench import flops

HERE = os.path.dirname(os.path.abspath(__file__))


def config(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_mean_keys_attended():
    # S=4, no window: positions see 1, 2, 3, 4 keys
    assert flops.mean_keys_attended(4, None) == 2.5
    # window 2: 1, 2, 2, 2
    assert flops.mean_keys_attended(4, 2) == 1.75
    # a window as long as the sequence changes nothing
    assert flops.mean_keys_attended(4096, 4096) == 2048.5
    assert flops.mean_keys_attended(4096, 8192) == 2048.5
    # S=8192 under Mistral's 4096: a ramp of 4096 then 4096 full windows
    assert flops.mean_keys_attended(8192, 4096) == \
        (4096 * 4097 / 2 + 4096 * 4096) / 8192


def test_mistral7b_l2_counts():
    cfg = config("mistral7b-l2")
    # one layer: q 4096x4096, k and v 4096x1024 each (8 KV heads of 128,
    # GQA), o 4096x4096, three SwiGLU matrices of 4096x14336
    layer = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert flops.lm_matmul_params(cfg) == 2 * layer + 32000 * 4096 \
        == 567_279_616
    # attention at S=4096 (window >= S): 2 layers x 6 products x 2 FLOPs
    # x 32 heads x 128 x 2048.5 keys
    attn = 2 * 6 * 2 * 32 * 128 * 2048.5
    assert flops.lm_attention_train_flops_per_token(cfg, 4096) == attn
    assert flops.lm_train_flops_per_token(cfg, 4096) == \
        6 * 567_279_616 + attn == pytest.approx(3.605e9, rel=1e-3)
    # window < S binds: at 8192 a query sees 3072.25 keys, not 4096.5
    assert flops.lm_attention_train_flops_per_token(cfg, 8192) == \
        2 * 6 * 2 * 32 * 128 * 3072.25


def test_resnet50_first_and_last_stage():
    cfg = config("resnet50")
    convs, head = flops.resnet_conv_layers(cfg)
    assert len(convs) == 1 + 16 * 3 + 4 and head == (2048, 1000)
    # stem: 7x7x3x64 at 112x112
    assert convs[0] == (7, 7, 3, 64, 112, 112)
    # first stage, first block, at 56x56: 1x1 64->64, 3x3 64->64,
    # 1x1 64->256 and the 1x1 64->256 projection
    assert convs[1:5] == [(1, 1, 64, 64, 56, 56), (3, 3, 64, 64, 56, 56),
                          (1, 1, 64, 256, 56, 56), (1, 1, 64, 256, 56, 56)]
    first_stage = sum(kh * kw * ci * co * oh * ow
                      for kh, kw, ci, co, oh, ow in convs[1:11])
    # by hand: block 1 = 56*56*(4096 + 36864 + 16384 + 16384), blocks 2
    # and 3 = 56*56*(16384 + 36864 + 16384) each
    assert first_stage == 56 * 56 * (73728 + 2 * 69632)
    # last stage: the stride-2 block reads 14x14x1024 and writes 7x7
    assert convs[-10:-6] == [
        (1, 1, 1024, 512, 14, 14), (3, 3, 512, 512, 7, 7),
        (1, 1, 512, 2048, 7, 7), (1, 1, 1024, 2048, 7, 7)]
    assert convs[-3:] == [(1, 1, 2048, 512, 7, 7), (3, 3, 512, 512, 7, 7),
                          (1, 1, 512, 2048, 7, 7)]
    # the whole: 4.09 GMACs forward (torchvision's resnet50 count)
    assert flops.resnet_train_flops_per_image(cfg) / 6 == \
        pytest.approx(4.09e9, rel=0.01)


def test_unknown_device_is_an_error():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        flops.peaks("TPU v9 imaginary")
