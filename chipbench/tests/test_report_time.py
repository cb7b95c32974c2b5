"""The readers of the two tables a step program's report gives beside
``scopes`` (``chipbench/report_time.py``): the grouped-matmul kernels in
``renamed`` by the phase of their recovered path, the asynchronous
collectives in ``collectives`` by mode; on tables and events made by
hand, and ``None`` for a report without the tables (an older commit)."""

import importlib.util
import os
import statistics

import pytest

from chipbench import report_time as rt
from chipbench import trace_reduce as tr
from chipbench.trace_reduce import Op

HERE = os.path.dirname(os.path.abspath(__file__))
STEP = "jit(prog)/hvd_step/loss_and_grad/"
FWD = STEP + "vmap(jvp(TransformerLM))/while/body/closed_call/periods/" \
    "layer_3/moe/while/body/closed_call/"
BWD = STEP + "vmap(transpose(jvp(TransformerLM)))/while/body/closed_call/" \
    "periods/periods/checkpoint/layer_3/moe/while/body/closed_call/"
REMAT = BWD.replace("checkpoint/", "checkpoint/rematted_computation/")
KERNEL = " = custom-call bf16[30720,768]" + tr.KERNEL_MARK

SCOPES = {
    "ragged-dot-none.1": FWD + "ragged-dot-none",
    "ragged-dot-metadata.1": FWD + "ragged-dot-metadata",
    "ragged-dot-none.2": BWD + "ragged-dot-none",
    "ragged-dot-none.3": REMAT + "ragged-dot-none",
    "ragged-dot-none.4": "ragged-dot-none",     # no scoped neighbour
    "other-kernel.5": FWD + "other-kernel",
    "fusion.6": FWD + "experts/mul",
}
RENAMED = {"ragged-dot-none.1": "ragged-dot-none",
           "ragged-dot-metadata.1": "ragged-dot-metadata",
           "ragged-dot-none.2": "ragged-dot-none",
           "ragged-dot-none.3": "ragged-dot-none",
           "other-kernel.5": "other-kernel"}


def ops(*rows, device=0, line=tr.OPS_LINE):
    return [Op(device, line, name, start * 1e-3, end * 1e-3)
            for name, start, end in rows]


def routed_step(device=0):
    """Milliseconds: forward kernel 2 + metadata 0.5, backward 3, remat
    1, and three events the readers must leave out."""
    return ops(("ragged-dot-metadata.1 = custom-call (s32[17])", 0, 0.5),
               ("ragged-dot-none.1" + KERNEL, 1, 3),
               ("fusion.6 = fusion bf16[30720,768]", 3, 4),
               ("ragged-dot-none.3" + KERNEL, 4, 5),
               ("ragged-dot-none.2" + KERNEL, 5, 8),
               ("ragged-dot-none.4" + KERNEL, 8, 9),
               ("other-kernel.5" + KERNEL, 9, 10), device=device)


def test_renamed_kernels_by_phase():
    forward = rt.renamed_ms(routed_step(), SCOPES, RENAMED, 1,
                            "ragged-dot", ("forward",))
    backward = rt.renamed_ms(routed_step(), SCOPES, RENAMED, 1,
                             "ragged-dot", ("backward", "remat"))
    assert forward == pytest.approx(2.5)
    assert backward == pytest.approx(4.0)
    # the mean over the chips, per traced step
    both = routed_step() + routed_step(device=1)[:2]
    assert rt.renamed_ms(both, SCOPES, RENAMED, 2, "ragged-dot",
                         ("forward",)) == pytest.approx(5.0 / 2 / 2)


def entry(instruction, mode, pair=None, nbytes=1 << 24):
    return {"instruction": instruction, "kind": "all-reduce",
            "bytes": nbytes, "mode": mode, "pair": pair,
            "path": "jit(prog)/hvd_step/grad_reduce/psum"}


COLLECTIVES = [
    entry("all-reduce.9", "sync", nbytes=32768),
    entry("async-collective-start", "start", "async-collective-done"),
    entry("fusion.341", "carried"),
    entry("async-collective-done", "done", "async-collective-start"),
    entry("async-collective-start.7", "start", "async-collective-done.7"),
    entry("fusion.394", "carried"),
    entry("fusion.395", "carried"),
    entry("async-collective-done.7", "done", "async-collective-start.7"),
]


def reducing_step(device=0, wait=1.0):
    """Two iterations of a loop's body (start, a carried product, done)
    inside a ``while``, then one pair after the loop with two carried
    fusions; ``wait`` stretches the last done."""
    body = []
    for at in (0.0, 10.0):
        body += [("async-collective-start = fusion (f32[8])", at, at + 0.1),
                 ("fusion.341 = fusion (f32[8])", at + 0.1, at + 4),
                 ("fusion.100 = fusion f32[8]", at + 4, at + 6),
                 ("async-collective-done = fusion f32[8]", at + 6, at + 6.5),
                 ("all-reduce.9 = all-reduce (f32[4096])", at + 7, at + 7.2)]
    after = [("async-collective-start.7 = fusion (f32[8])", 20, 20.2),
             ("fusion.394 = fusion (f32[8])", 20.2, 22),
             ("fusion.395 = fusion (f32[8])", 22, 25),
             ("async-collective-done.7 = fusion f32[8]", 25, 25 + wait)]
    return ops(("while.1 = while (s32[])", 0, 17.2), *body, *after,
               device=device)


def test_collectives_by_mode():
    one = reducing_step()
    assert rt.mode_ms(one, COLLECTIVES, 1, "done") == pytest.approx(2.0)
    assert rt.mode_ms(one, COLLECTIVES, 1, "carried", over=statistics.fmean) \
        == pytest.approx(2 * 3.9 + 1.8 + 3.0)
    # the k-th start with the k-th done; a sync collective is in neither
    assert rt.in_flight_ms(one, COLLECTIVES, 1) \
        == pytest.approx(6.5 + 6.5 + 6.0)
    # the wait and the span on the worst chip, the carried time a mean
    two = one + reducing_step(device=1, wait=3.0)
    assert rt.mode_ms(two, COLLECTIVES, 1, "done") == pytest.approx(4.0)
    assert rt.in_flight_ms(two, COLLECTIVES, 1) == pytest.approx(21.0)
    assert rt.mode_ms(two, COLLECTIVES, 1, "carried", over=statistics.fmean) \
        == pytest.approx(12.6)
    # a trace cut before a done: the open start is left out
    cut = [op for op in one if "done.7" not in op.name]
    assert rt.in_flight_ms(cut, COLLECTIVES, 1) == pytest.approx(13.0)
    assert rt.mode_ms(one, [], 1, "done") == 0.0


# ---- the readers

def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(os.path.dirname(HERE),
                                       "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


TRACED = ["moe_experts_forward_ms_per_step",
          "moe_experts_backward_ms_per_step",
          "grad_reduce_wait_ms_per_step",
          "grad_reduce_async_span_ms_per_step",
          "grad_reduce_carried_ms_per_step"]


def ctx_of(listed, ranks=1):
    return {"trace": listed, "trace_steps": 1, "ranks": ranks,
            "counters": {"window_start": {}, "window_end": {}}}


def with_reports(monkeypatch, *reports):
    from horovod_tpu import telemetry

    monkeypatch.setattr(telemetry, "program_reports",
                        lambda: list(reports))


def test_readers_read_the_tables(monkeypatch):
    with_reports(monkeypatch, {
        "module": "jit_prog", "scopes": SCOPES, "renamed": RENAMED,
        "collectives": COLLECTIVES})
    listed = routed_step() + [
        op._replace(start=op.start + 1, end=op.end + 1)
        for op in reducing_step()]
    ctx = ctx_of(listed)
    got = {name: reader(name).read(ctx) for name in TRACED}
    assert got == pytest.approx({
        "moe_experts_forward_ms_per_step": 2.5,
        "moe_experts_backward_ms_per_step": 4.0,
        "grad_reduce_wait_ms_per_step": 2.0,
        "grad_reduce_async_span_ms_per_step": 19.0,
        "grad_reduce_carried_ms_per_step": 12.6})


@pytest.mark.parametrize("name", TRACED)
def test_reader_returns_none_without_its_table(name, monkeypatch):
    """The parent's report has ``scopes`` alone; a one-chip program's
    ``collectives`` and a dense model's ``renamed`` are empty; a run
    with ``--trace 0`` has no events: ``None`` each time, never 0."""
    read = reader(name).read
    listed = routed_step() + reducing_step()
    with_reports(monkeypatch, {"module": "jit_prog", "scopes": SCOPES})
    assert read(ctx_of(listed)) is None
    with_reports(monkeypatch, {"module": "jit_prog", "scopes": SCOPES,
                               "renamed": {}, "collectives": []})
    assert read(ctx_of(listed)) is None
    with_reports(monkeypatch)
    assert read(ctx_of(listed)) is None
    with_reports(monkeypatch, {
        "module": "jit_prog", "scopes": SCOPES, "renamed": RENAMED,
        "collectives": COLLECTIVES})
    assert read(ctx_of(None)) is None


def test_start_up_readers_give_seconds_a_rank():
    ctx = ctx_of(None, ranks=4)
    ctx["counters"]["window_start"] = {
        "horovod_init_seconds_total": 0.75,
        "horovod_init_state_seconds_total": 10.0}
    assert reader("hvd_init_s").read(ctx) == 0.75   # once a process
    assert reader("init_state_s").read(ctx) == 2.5
    # a program without the counters reads 0 there: nothing to report
    ctx["counters"]["window_start"] = {
        "horovod_init_seconds_total": 0.0,
        "horovod_init_state_seconds_total": 0.0}
    assert reader("hvd_init_s").read(ctx) is None
    assert reader("init_state_s").read(ctx) is None
