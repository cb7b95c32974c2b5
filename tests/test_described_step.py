"""``tools/described_step.layout_copies``: the counter of the copies that
transpose a leaf of the state through HBM around the optimizer (PERF.md
section 6, PR 49), over a planted program text in the shape the TPU
compiler prints (names, layouts with tiles and memory spaces, the
header's donations, ``/*index=5*/`` comments in a long tuple)."""

import numpy as np
import pytest

from tools import described_step

_BIG = "f32[1,2048,8192]"        # 67 MB
PLANTED = "\n".join([
    "HloModule jit_prog, is_scheduled=true, input_output_alias={ {0}: (0, {},"
    " may-alias), {1}: (1, {}, may-alias), {2}: (2, {}, may-alias), {3}: (3,"
    " {}, may-alias), {4}: (4, {}, may-alias), {5}: (5, {}, may-alias) },"
    " entry_computation_layout={()->()}",
    "",
    "%fused_computation.7 (param_0.1: f32[1,2048,8192]) -> (f32[1,2048,8192],"
    " f32[1,2048,8192]) {",
    f"  %param_0.1 = {_BIG}{{1,2,0:T(8,128)}} parameter(0)",
    # a copy inside a fusion has a fusion's name outside: not an
    # instruction of the schedule, yet it transposes all the same
    f"  %copy.1 = {_BIG}{{2,1,0:T(8,128)}} copy(%param_0.1)",
    f"  ROOT %tuple.1 = ({_BIG}{{2,1,0:T(8,128)}}, {_BIG}{{2,1,0:T(8,128)}})"
    " tuple(%copy.1, %copy.1)",
    "}",
    "",
    "ENTRY %main.9 (state__opt_state___0__count.1: s32[]) -> (s32[]) {",
    "  %state__opt_state___0__count.1 = s32[]{:T(128)} parameter(0)",
    f"  %state__opt_state___0__mu__periods____layer_0____mlp____wi_up____"
    f"kernel__.1 = {_BIG}{{2,1,0:T(8,128)}} parameter(1), "
    "metadata={op_name=\"state[\\'opt_state\\'][0].mu[\\'periods\\']\"}",
    f"  %state__opt_state___0__nu__periods____layer_0____mlp____wi_up____"
    f"kernel__.1 = {_BIG}{{2,1,0:T(8,128)}} parameter(2)",
    f"  %state__params____periods____layer_0____mlp____wi_up____kernel__.1 "
    f"= {_BIG}{{2,1,0:T(8,128)}} parameter(3)",
    "  %state__params____periods____layer_0____attn____wq____kernel__.1 = "
    "f32[1,2048,4,128]{3,2,1,0:T(4,128)} parameter(4)",
    "  %state__params____periods____layer_0____ln_mlp____scale__.1 = "
    "f32[1,2048]{1,0:T(1,128)} parameter(5)",
    "  %batch.1 = s32[1,2,8192]{2,1,0:T(2,128)} parameter(6)",
    # on the way in: named by its operand
    f"  %copy.10 = {_BIG}{{1,2,0:T(8,128)}} copy(%state__opt_state___0__mu__"
    "periods____layer_0____mlp____wi_up____kernel__.1)",
    # on the way in, behind a prefetch: named by its op_name alone
    f"  %copy-start.3 = ({_BIG}{{2,1,0:T(8,128)S(1)}}, {_BIG}{{2,1,0:T(8,128)"
    "}, u32[]{:S(2)}) copy-start(%state__opt_state___0__nu__periods____"
    "layer_0____mlp____wi_up____kernel__.1)",
    f"  %copy-done.3 = {_BIG}{{2,1,0:T(8,128)S(1)}} copy-done(%copy-start.3)",
    f"  %copy.11 = {_BIG}{{1,2,0:T(8,128)}} copy(%copy-done.3), "
    "sharding={replicated}, metadata={op_name=\"state[\\'opt_state\\'][0]"
    ".nu[\\'periods\\'][\\'layer_0\\'][\\'mlp\\'][\\'wi_up\\'][\\'kernel\\']"
    "\"}",
    f"  %copy.12 = {_BIG}{{1,2,0:T(8,128)}} copy(%state__params____periods__"
    "__layer_0____mlp____wi_up____kernel__.1)",
    # the same layout (a donated buffer kept apart, a move to the
    # on-chip memory): a copy, and no transpose
    f"  %copy.13 = {_BIG}{{2,1,0:T(8,128)S(1)}} copy(%state__params____"
    "periods____layer_0____mlp____wi_up____kernel__.1)",
    f"  %fusion.922 = ({_BIG}{{1,2,0:T(8,128)}}, {_BIG}{{1,2,0:T(8,128)}}, "
    f"{_BIG}{{1,2,0:T(8,128)}}) fusion(%copy.10, %copy.11, %copy.12), "
    "kind=kOutput, calls=%fused_computation.7",
    f"  %get-tuple-element.1 = {_BIG}{{1,2,0:T(8,128)}} "
    "get-tuple-element(%fusion.922), index=0",
    f"  %get-tuple-element.2 = {_BIG}{{1,2,0:T(8,128)}} "
    "get-tuple-element(%fusion.922), index=1",
    f"  %get-tuple-element.3 = {_BIG}{{1,2,0:T(8,128)}} "
    "get-tuple-element(%fusion.922), index=2",
    # on the way out: no name, the output it becomes tells
    f"  %copy.20 = {_BIG}{{2,1,0:T(8,128)}} copy(%get-tuple-element.1)",
    f"  %copy.21 = {_BIG}{{2,1,0:T(8,128)}} copy(%get-tuple-element.2)",
    f"  %copy.22 = {_BIG}{{2,1,0:T(8,128)}} copy(%get-tuple-element.3)",
    # a kernel of exactly 4 MiB counts, a norm's scale does not
    "  %copy.30 = f32[1,2048,4,128]{1,3,2,0:T(8,128)} copy(%state__params__"
    "__periods____layer_0____attn____wq____kernel__.1)",
    "  %copy.31 = f32[1,2048]{0,1:T(1,128)} copy(%state__params____periods__"
    "__layer_0____ln_mlp____scale__.1)",
    # not of the state: the attention scores' operand, and a bfloat16 one
    "  %fusion.5 = f32[1,2,32,8192,128]{4,3,2,1,0:T(8,128)} fusion(%batch.1),"
    " kind=kLoop, calls=%fused_computation.7",
    "  %copy.40 = f32[1,2,32,8192,128]{4,2,3,1,0:T(8,128)} copy(%fusion.5), "
    "metadata={op_name=\"jit(prog)/hvd_step/loss_and_grad/attn/transpose\"}",
    "  %fusion.6 = bf16[1,2,32,8192,128]{4,3,2,1,0:T(8,128)(2,1)} "
    "fusion(%batch.1), kind=kLoop, calls=%fused_computation.7",
    "  %copy.41 = bf16[1,2,32,8192,128]{4,2,3,1,0:T(8,128)(2,1)} "
    "copy(%fusion.6)",
    "  ROOT %tuple.9 = (s32[]{:T(128)}, " + ", ".join(
        [f"{_BIG}{{2,1,0:T(8,128)}}"] * 3) + ", /*index=4*/f32[1,2048,4,128]"
    "{3,2,1,0:T(4,128)}, f32[1,2048]{1,0:T(1,128)}) tuple("
    "%state__opt_state___0__count.1, %copy.20, %copy.21, %copy.22, "
    "/*index=4*/%state__params____periods____layer_0____attn____wq____"
    "kernel__.1, %copy.31)",
    "}",
])

#: instruction -> what it copies, None where it is not counted
WANT = {
    "copy.1": "other", "copy.10": "mu", "copy.11": "nu",
    "copy.12": "parameter", "copy.13": None, "copy.20": "mu",
    "copy.21": "nu", "copy.22": "parameter", "copy.30": "parameter",
    "copy.31": None, "copy.40": "other", "copy.41": None,
}


@pytest.mark.parametrize("instruction", sorted(WANT))
def test_layout_copies_over_a_planted_program(instruction):
    found = {name: rest for name, *rest
             in described_step.layout_copies(PLANTED)}
    assert set(found) == {name for name, what in WANT.items() if what}
    if WANT[instruction] is None:
        return
    result, source, what, size, fused = found[instruction]
    assert what == WANT[instruction]
    assert fused == (instruction == "copy.1")
    shape, layout = result.rstrip("}").split("{")
    assert size == 4 * np.prod([int(n) for n in shape[4:-1].split(",")])
    assert layout != source and ":" not in layout


def test_layout_copies_takes_a_smaller_floor():
    """Every float32 copy that changes the layout, the norm's too."""
    found = described_step.layout_copies(PLANTED, min_bytes=8192)
    assert {name for name, *_ in found} == set(WANT) - {"copy.13", "copy.41"}
