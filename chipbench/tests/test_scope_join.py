"""The join between a device trace and the step program's own table
(``chipbench/scope_join.py``): on a table and intervals made by hand,
on the traces recorded on the chip (tests/data, and tests/data_scopes
with the program's table beside the events), and every reader that
rests on it returning ``None`` where the program's names are missing."""

import glob
import importlib.util
import json
import os

import pytest

from chipbench import scope_join as sj
from chipbench import trace_reduce as tr
from chipbench.trace_reduce import Op

HERE = os.path.dirname(os.path.abspath(__file__))
STEP = "jit(prog)/hvd_step/loss_and_grad/"
FWD = STEP + "vmap(jvp(TransformerLM))/while/body/closed_call/layers/"
BWD = STEP + "vmap(transpose(jvp(TransformerLM)))/while/body/" \
    "closed_call/checkpoint/"
KERNEL = " = custom-call bf16[8]" + tr.KERNEL_MARK

TABLE = {
    "fusion.1": FWD + "mlp/wi_up/dot_general",
    "attn.1": FWD + "attn/flash_fwd/flash_fwd",
    "copy.2": FWD + "attn/flash_fwd/transpose",     # in the scope, no kernel
    "fusion.3": BWD + "layers/mlp/wo/dot_general",
    "attn.2": BWD + "layers/attn/flash_dkv/flash_dkv",
    "fusion.4": BWD + "rematted_computation/layers/ln_attn/mul",
    "fusion.5": STEP + "vmap(transpose(jvp(lm_head_ce)))/while/body/"
    "closed_call/checkpoint/rematted_computation/bcm,vm->bcv/dot_general",
    "gather.6": STEP + "vmap(jvp(TransformerLM))/embed/gather",
    "fusion.7": "jit(prog)/hvd_step/optimizer/mul",
    "psum.8": "jit(prog)/hvd_step/grad_reduce/psum",
    "pmean.9": "jit(prog)/hvd_step/aux_reduce/psum",
    # merged by the compiler: the path under the step's scope counts
    "fusion.10": "x;" + STEP + "vmap(jvp(TransformerLM))/ln_final/mul",
    "convert.11": "",                   # the compiler's own, no name
    "while.12": FWD + "while",
}


def ops(*rows, device=0, line=tr.OPS_LINE):
    return [Op(device, line, name, start, end) for name, start, end in rows]


def one_step(device=0):
    """Thirteen leaf operations of 1 ms each, inside a ``while`` that
    only covers them, and one the table does not hold."""
    names = ["fusion.1 = fusion f32[4]", "attn.1" + KERNEL,
             "copy.2 = copy bf16[8]", "fusion.3 = fusion f32[4]",
             "attn.2" + KERNEL, "fusion.4 = fusion f32[4]",
             "fusion.5 = fusion f32[4]", "gather.6 = gather f32[4]",
             "fusion.7 = fusion f32[4]", "psum.8 = all-reduce f32[4]",
             "pmean.9 = all-reduce f32[]", "fusion.10 = fusion f32[4]",
             "convert.11 = convert bf16[4]", "unknown.99 = fusion f32[4]"]
    rows = [(name, i * 1e-3, (i + 1) * 1e-3) for i, name in enumerate(names)]
    rows.append(("while.12 = while (s32[])", 0.0, 14e-3))
    return ops(*rows, device=device)


def test_phase_of_a_path():
    assert sj.phase_of(FWD + "mlp/wo/dot_general") == "forward"
    assert sj.phase_of(BWD + "layers/mlp/wo/dot_general") == "backward"
    assert sj.phase_of(BWD + "rematted_computation/layers/mlp/mul") \
        == "remat"
    assert sj.phase_of("jit(prog)/hvd_step/optimizer/add") == "optimizer"
    assert sj.phase_of("jit(prog)/hvd_step/grad_reduce/psum") == "reduce"
    assert sj.phase_of("jit(prog)/hvd_step/aux_reduce/psum") == "reduce"
    # the shard_map program of rank threads names the same scopes
    assert sj.phase_of("jit(prog)/jit(shmap_body)/hvd_step/loss_and_grad/"
                       "transpose(jvp(ResNet50))/conv") == "backward"
    assert sj.phase_of("") == sj.phase_of(None) == "unattributed"
    assert sj.phase_of("jit(prog)/vmap(jvp(TransformerLM))/mul") \
        == "unattributed"                   # an older commit's names
    assert sj.step_path("a;" + FWD + "x") == FWD + "x"
    assert sj.step_path("a;b") == "a" and sj.step_path("") == ""


def test_split_on_a_hand_made_table():
    found = sj.split(one_step(), TABLE, trace_steps=1)
    assert found["phase"] == pytest.approx({
        "forward": 5.0,         # fusion.1 attn.1 copy.2 gather.6 fusion.10
        "backward": 2.0,        # fusion.3 attn.2
        "remat": 2.0,           # fusion.4 fusion.5
        "optimizer": 1.0, "reduce": 2.0,
        "unattributed": 2.0})   # convert.11 (no name), unknown.99 (no entry)
    # the envelope is no leaf: the parts add up to the busy time
    assert found["total"] == pytest.approx(14.0)
    assert found["total"] == pytest.approx(
        1e3 * tr.busy_seconds(one_step())[0])
    assert found["part"] == pytest.approx({
        "mlp": 2.0, "attention": 3.0, "loss_head": 2.0})
    # a kernel's row is the Pallas call, not what else its scope holds
    assert found["kernel"] == pytest.approx({
        "flash_fwd": 1.0, "flash_dkv": 1.0})
    assert sorted(name for name, _ in found["unattributed"]) == [
        "convert.11 = convert bf16[4]", "unknown.99 = fusion f32[4]"]


def test_split_is_the_mean_over_chips_per_traced_step():
    two_steps = one_step() + [
        op._replace(start=op.start + 1.0, end=op.end + 1.0)
        for op in one_step()]
    both = two_steps + [op._replace(device=1) for op in two_steps]
    assert sj.split(both, TABLE, trace_steps=2)["phase"] == pytest.approx(
        sj.split(one_step(), TABLE, trace_steps=1)["phase"])


def test_traced_module_is_the_busiest_program():
    listed = ops(("jit_prog(77)", 0.0, 5.0), ("jit_prog(77)", 5.0, 9.0),
                 ("jit_checksum(3)", 9.0, 9.5), line=tr.MODULES_LINE)
    assert sj.traced_module(listed + one_step()) == "jit_prog"
    assert sj.traced_module(one_step()) is None


def recorded(path):
    with open(path) as f:
        data = json.load(f)
    return data, [Op(d, line, name, s * 1e-9, e * 1e-9)
                  for d, line, name, s, e in data["events"]]


def test_split_on_the_step_recorded_with_its_table():
    """One whole step of mistral7b-s4k-1chip with the table its program
    gave of itself on the chip: the phases add up to the busy time, the
    names reach 95% of it, and each flash kernel is a row.  The step
    was recorded before PR 28 and ran a third flash kernel, ``flash_dq``,
    which no program has any more and no reader reads: its time is read
    from the recording by its old name."""
    data, listed = recorded(os.path.join(
        HERE, "data_scopes", "mistral7b-s4k-1chip.json"))
    found = sj.split(listed, data["scopes"], trace_steps=1)
    busy_ms = 1e3 * tr.busy_seconds(listed)[0]
    assert found["total"] == pytest.approx(busy_ms, rel=0.01)
    assert found["phase"]["unattributed"] < 0.05 * found["total"]
    assert found["phase"] == pytest.approx(data["expect"]["phase_ms"])
    assert found["part"] == pytest.approx(data["expect"]["part_ms"])
    old_dq_ms = data["expect"]["kernel_ms"]["flash_dq"]
    assert found["kernel"] == pytest.approx(
        {k: data["expect"]["kernel_ms"][k] for k in sj.KERNELS})
    assert all(v > 0 for v in found["kernel"].values())
    assert found["phase"]["backward"] > found["phase"]["forward"] \
        > found["phase"]["remat"] > found["phase"]["optimizer"] > 0
    # the flash kernels are what flash_roofline takes as one: the two
    # that remain, and in this recording the old dq kernel
    kernels = tr.matching_seconds(listed, r"\[tpu_custom_call\]$")[0]
    assert old_dq_ms == pytest.approx(1e3 * sum(
        op.end - op.start for op in tr.leaf_ops(listed)[0]
        if "flash_dq/" in (data["scopes"].get(
            sj.instruction_of(op.name)) or "")
        and op.name.endswith(tr.KERNEL_MARK)))
    assert sum(found["kernel"].values()) + old_dq_ms \
        == pytest.approx(1e3 * kernels)
    assert sj.traced_module(listed) == "jit_prog"


@pytest.mark.parametrize("path", sorted(
    glob.glob(os.path.join(HERE, "data", "*.json"))), ids=os.path.basename)
def test_an_older_programs_trace_is_all_unattributed(path):
    """The traces PR 24 recorded are of a program without the names:
    joined with a table that holds only what that program's
    instructions could say (no ``hvd_step/`` scope), everything is
    unattributed, and still adds up to the busy time."""
    _, listed = recorded(path)
    table = {sj.instruction_of(op.name): "jit(prog)/vmap(jvp(f))/mul"
             for op in listed if op.line == tr.OPS_LINE}
    assert not sj.has_step_scopes(table)
    found = sj.split(listed, table, trace_steps=1)
    leaf_ms = 1e3 * sum(
        sum(op.end - op.start for op in ops_)
        for ops_ in tr.leaf_ops(listed).values()) / len(tr.leaf_ops(listed))
    assert found["phase"]["unattributed"] == pytest.approx(leaf_ms)
    assert found["total"] == pytest.approx(leaf_ms)
    assert not any(found["part"].values()) \
        and not any(found["kernel"].values())


# ---- the readers

READERS = sorted(
    os.path.basename(p)[:-3] for p in glob.glob(os.path.join(
        os.path.dirname(HERE), "layer_metrics", "*.py")))
JOINED = ["forward_ms_per_step", "backward_ms_per_step",
          "remat_ms_per_step", "optimizer_ms_per_step",
          "scope_unattributed_pct", "mlp_ms_per_step",
          "attention_ms_per_step", "loss_head_ms_per_step",
          "flash_fwd_ms_per_step", "flash_dkv_ms_per_step",
          "step_program_hbm_gb"]
COUNTED = ["step_rendezvous_wait_ms", "step_stage_batch_ms",
           "step_program_call_ms", "step_trace_lower_s",
           "step_compile_or_cache_s"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(os.path.dirname(HERE),
                                       "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def ctx_with(reports, listed, counters=None, steps=40, ranks=1):
    """A run's ``ctx`` as ``run.py`` hands it to the readers, with the
    program's reports as ``reports``."""
    names = {n for r in COUNTED for n in reader(r).COUNTERS}
    zero = dict.fromkeys(names, 0.0)
    return {"trace": listed, "trace_steps": 1, "ranks": ranks,
            "window": {"steps": steps},
            "counters": {"window_start": dict(zero),
                         "window_end": {**zero, **(counters or {})}}}


def test_every_new_reader_is_listed_in_the_benchmark():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]}
    assert set(JOINED + COUNTED) <= listed
    assert listed == set(READERS)


@pytest.mark.parametrize("name", JOINED)
def test_joined_reader_returns_none_without_the_names(name, monkeypatch):
    """A program without ``program_reports`` (an older commit), one that
    kept no program, and one whose table has no ``hvd_step/`` scope (an
    executable another commit compiled) give ``None``, never 0."""
    from horovod_tpu import telemetry

    data, listed = recorded(os.path.join(
        HERE, "data_scopes", "mistral7b-s4k-1chip.json"))
    read = reader(name).read
    older = {"module": "jit_prog", "memory": data["memory"],
             "scopes": {k: v.replace("hvd_step/loss_and_grad/", "")
                        .replace("hvd_step/optimizer/", "")
                        for k, v in data["scopes"].items()}}
    monkeypatch.setattr(telemetry, "program_reports", lambda: [older])
    assert read(ctx_with(None, listed)) is None
    monkeypatch.setattr(telemetry, "program_reports", lambda: [])
    assert read(ctx_with(None, listed)) is None
    monkeypatch.delattr(telemetry, "program_reports")
    assert read(ctx_with(None, listed)) is None
    # and with no trace there is nothing to join
    if name != "step_program_hbm_gb":
        monkeypatch.setattr(telemetry, "program_reports",
                            lambda: [dict(older, scopes=data["scopes"])],
                            raising=False)
        assert read(ctx_with(None, None)) is None


def test_joined_readers_read_the_recorded_step(monkeypatch):
    from horovod_tpu import telemetry

    data, listed = recorded(os.path.join(
        HERE, "data_scopes", "mistral7b-s4k-1chip.json"))
    mine = {"module": "jit_prog", "scopes": data["scopes"],
            "memory": data["memory"]}
    other = {"module": "jit_checksum", "memory": data["memory"],
             "scopes": {"a.1": "jit(checksum)/hvd_step/optimizer/x"}}
    asked = []
    monkeypatch.setattr(
        telemetry, "program_reports",
        lambda: asked.append(1) or [None, mine, other])
    ctx = ctx_with(None, listed)
    got = {name: reader(name).read(ctx) for name in JOINED}
    assert len(asked) == 1              # one compile a run, not twelve
    expect = data["expect"]
    assert got["forward_ms_per_step"] == pytest.approx(
        expect["phase_ms"]["forward"])
    assert got["remat_ms_per_step"] == pytest.approx(
        expect["phase_ms"]["remat"])
    assert got["mlp_ms_per_step"] == pytest.approx(expect["part_ms"]["mlp"])
    assert got["flash_dkv_ms_per_step"] == pytest.approx(
        expect["kernel_ms"]["flash_dkv"])
    assert 0 < got["scope_unattributed_pct"] < 5
    m = data["memory"]
    assert got["step_program_hbm_gb"] == pytest.approx(
        (m["argument"] + m["temp"] + m["output"] - m["alias"]
         + m["generated_code"]) / 1e9)
    assert all(v is not None and v > 0 for v in got.values())


def test_counted_readers():
    calls = "horovod_step_calls_total"
    moved = {calls: 160.0,
             "horovod_step_rendezvous_wait_seconds_total": 0.48,
             "horovod_step_stage_batch_seconds_total": 0.02,
             "horovod_step_program_call_seconds_total": 0.06}
    ctx = ctx_with(None, None, moved, steps=40, ranks=4)
    assert reader("step_rendezvous_wait_ms").read(ctx) \
        == pytest.approx(3.0)           # per step and rank
    assert reader("step_stage_batch_ms").read(ctx) == pytest.approx(0.5)
    assert reader("step_program_call_ms").read(ctx) == pytest.approx(1.5)
    # a batch placed once: the steps stage nothing, and that is a reading
    placed = ctx_with(None, None, {calls: 40.0}, steps=40)
    assert reader("step_stage_batch_ms").read(placed) == 0.0
    # an older commit counts no step calls: nothing to read
    older = ctx_with(None, None)
    for name in COUNTED:
        assert reader(name).read(older) is None, name
    started = ctx_with(None, None)
    started["counters"]["window_start"].update({
        "horovod_compile_trace_seconds_total": 1.5,
        "horovod_compile_lower_seconds_total": 0.5,
        "horovod_compile_backend_seconds_total": 0.25,
        "horovod_compile_cache_read_seconds_total": 1.0})
    assert reader("step_trace_lower_s").read(started) == 2.0
    assert reader("step_compile_or_cache_s").read(started) == 1.25
