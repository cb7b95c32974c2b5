"""The reduction from a trace to numbers: on intervals worked by hand,
and on a small trace recorded on the chip (tests/data)."""

import glob
import json
import os

import pytest

from chipbench import trace_reduce as tr
from chipbench.trace_reduce import Op

HERE = os.path.dirname(os.path.abspath(__file__))


def ops(*rows, device=0, line=tr.OPS_LINE):
    return [Op(device, line, name, start, end) for name, start, end in rows]


def test_union_and_idle_share():
    listed = ops(("a", 0.0, 1.0), ("b", 0.5, 2.0), ("c", 3.0, 4.0))
    assert tr.union_seconds([(o.start, o.end) for o in listed]) == 3.0
    assert tr.window(listed) == (0.0, 4.0)
    assert tr.busy_seconds(listed) == {0: 3.0}
    assert tr.idle_share(listed) == {0: 0.25}
    # a second chip is measured against the same window
    both = listed + ops(("a", 0.0, 1.0), device=1)
    assert tr.idle_share(both) == {0: 0.25, 1: 0.75}
    # the modules line and the host's annotations are not device ops
    both += ops(("module", 0.0, 9.0), line=tr.MODULES_LINE)
    both += ops(("chipbench: x", 0.0, 9.0), device=-1, line=tr.HOST_LINE)
    assert tr.busy_seconds(both) == {0: 3.0, 1: 1.0}
    assert tr.host_spans(both) == [("chipbench: x", 0.0, 9.0)]


def test_kernel_sum():
    kernel = ('custom-call(bf16[8]{0} %p), '
              'custom_call_target="tpu_custom_call"')
    assert tr.short_name(
        f"%attn.21 = (bf16[8,4]{{1,0:T(8,128)(2,1)}}, f32[8]{{0}}) {kernel}"
    ) == "attn.21 = custom-call (bf16[8,4], f32[8]) [tpu_custom_call]"
    assert tr.short_name("%fusion.3 = f32[4]{0:T(4)S(1)} fusion(f32[4] %p)") \
        == "fusion.3 = fusion f32[4]"
    assert tr.short_name("jit_prog(123)") == "jit_prog(123)"
    # an instruction is named after its jax primitive, not its operation
    assert tr.short_name(
        "%psum.84 = f32[320,4]{1,0:T(8,128)} all-reduce(f32[320,4]{1,0} %x), "
        "channel_id=1, replica_groups={{0,1,2,3}}"
    ) == "psum.84 = all-reduce f32[320,4]"
    assert tr.ENVELOPE.search(tr.short_name(
        "%while.13 = (s32[]{:T(128)}, bf16[1,4]{1,0}) while(%tuple)"))
    fwd = "attn.21 = custom-call (bf16[8]) [tpu_custom_call]"
    listed = ops(("while.2 = while (s32[])", 0.0, 4.5),     # covers its body
                 (fwd, 0.0, 1.0),
                 ("fusion.3 = fusion f32[4]", 1.0, 3.0),
                 ("attn.22 = custom-call bf16[8] [tpu_custom_call]", 3.0, 3.5),
                 (fwd, 4.0, 4.25))
    assert tr.matching_seconds(listed, r"\[tpu_custom_call\]$") == {0: 1.75}
    assert tr.matching_seconds(listed, r"^fusion") == {0: 2.0}
    assert tr.top_ops(listed, n=2) == [["fusion.3 = fusion f32[4]", 2.0],
                                       [fwd, 1.25]]
    assert tr.busy_seconds(listed) == {0: 4.5}


def test_idle_gaps_are_named_by_the_host_span_over_them():
    listed = ops(("a", 0.0, 1.0), ("b", 3.0, 4.0), ("c", 4.5, 5.0))
    spans = [("enqueue", 0.9, 1.2), ("wait", 1.2, 4.6)]
    assert tr.idle_gaps(listed, spans) == [["wait", 2.0], ["wait", 0.5]]
    assert tr.idle_gaps(listed, []) == [
        ["no span of the benchmark", 2.0], ["no span of the benchmark", 0.5]]


def recorded():
    return sorted(glob.glob(os.path.join(HERE, "data", "*.json")))


@pytest.mark.parametrize("path", recorded(),
                         ids=[os.path.basename(p) for p in recorded()])
def test_recorded_trace(path):
    """A few steps as the chip recorded them (tools/trace_dump.py): the
    numbers the readers take from it, worked out once by hand from the
    file and kept beside it under "expect"."""
    with open(path) as f:
        record = json.load(f)
    listed = [Op(d, line, name, s * 1e-9, e * 1e-9)
              for d, line, name, s, e in record["events"]]
    expect = record["expect"]
    busy = tr.busy_seconds(listed)
    assert sorted(busy) == expect["devices"]
    assert max(tr.idle_share(listed).values()) == \
        pytest.approx(expect["idle_share_worst"], rel=1e-6)
    assert max(tr.matching_seconds(listed, expect["kernel"]).values()) == \
        pytest.approx(expect["kernel_seconds"], rel=1e-6)
    # PR 24's program reduced its gradients in synchronous all-reduces
    # after the backward, each an event of the ops line: read by name
    # (no reader does since PR 41; today's are asynchronous fusions,
    # read through the program's table: test_report_time.py)
    assert max(tr.matching_seconds(listed, " = all-reduce ").values()) \
        == pytest.approx(expect["collective_seconds"], abs=1e-9)
    assert tr.top_ops(listed, n=1)[0][0] == expect["top_op"]
