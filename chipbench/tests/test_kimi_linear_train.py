"""What the ``kimi_linear_train`` configuration (Kimi-Linear-48B-A3B)
brings: its parameter count against the program's own tree, its widths
against the catalog's row, its FLOP and byte counts against hand-worked
ones, its mix against ``s8k-1chip-settled``, its plain reference against
the program at the rehearsal sizes, the fp8 control failing the
rehearsal's limits, the cell's rehearsal end to end, and each new reader
on the scope table recorded on the chip (``data_scopes``) and on tables
made by hand."""

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from chipbench import flops, kimi_linear_flops, weights
from chipbench import trace_reduce as tr
from chipbench.run import gaps, metrics_of, with_rehearsal
from chipbench.trace_reduce import Op

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "kimi-linear-48b-s8k-1chip"
CONFIG = "kimi-linear-48b-l5-ep32"
MIX = "s8k-1chip-settled-ep32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("kda_ms_per_step", "kda_scan_ms_per_step", "kda_conv_ms_per_step",
       "kda_scan_roofline", "kda_tokens_per_chunk", "mla_ms_per_step",
       "mla_flash_roofline")


def load(directory, name, rehearse):
    with open(os.path.join(HERE, "..", directory, name + ".json")) as f:
        return with_rehearsal(json.load(f), rehearse)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(HERE, "..", "layer_metrics",
                                       name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ---------------------------------------------------------------------------
# the configuration and its operations

def test_flops_and_bytes_against_a_hand_worked_count():
    """The five layers as the cell runs them, at 8,192 tokens a row, at
    the PUBLISHED widths."""
    config = load("configs", CONFIG, False)
    assert kimi_linear_flops.kda_sizes(config) == (32, 128, 4096, 4)
    # the rule by its recurrence: S^T k, k u^T, S^T q, each 128 x 128
    # multiply-adds a head
    rule = 3 * 2 * 32 * 128 * 128
    assert kimi_linear_flops.rule_flops_per_token(config) == rule == 3145728
    projections = 2304 * (3 * 4096 + 2 * 128 + 32) + 2 * 128 * 4096
    kda = 2 * projections + 3 * 2 * 4 * 4096 + rule + 2 * 4096 * 2304
    assert kimi_linear_flops.kda_layer_flops_per_token(config) == kda \
        == 82165760
    mla = 2 * (2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256
               + 32 * 128 * 2304)
    assert kimi_linear_flops.mla_projection_flops_per_token(config) == mla \
        == 58228736
    # the one latent layer's query sees 4,096.5 keys on average: QK^T at
    # 192, PV at 128, nothing padded
    attended = 3 * 2 * 32 * (192 + 128) * 4096.5
    assert kimi_linear_flops.attention_train_flops_per_token(config, 8192) \
        == attended == 251688960
    assert kimi_linear_flops.held_assignments_per_token(config) == 0.25
    expert = 3 * 2304 * 1024
    expert_layer = 2 * (2304 * 256 + expert * (1 + 0.25))
    dense = 2 * 3 * 2304 * 9216
    head = 2 * 20480 * 2304
    total = kimi_linear_flops.train_flops_per_token(config, 8192)
    assert total == 3 * (4 * kda + mla + dense + 4 * expert_layer + head) \
        + attended == 2304178176
    # by FLOPs: the held experts 1.8%, the rule's own products 1.6%, the
    # causal 8k block 10.9%, the head 12.3%
    assert 4 * 0.25 * 18 * 2304 * 1024 / total == pytest.approx(0.0184,
                                                                abs=0.0005)
    assert kimi_linear_flops.grouped_products_train_flops_per_assignment(
        config) == 18 * 2304 * 1024
    assert 3 * 4 * rule / total == pytest.approx(0.0164, abs=0.0005)
    assert attended / total == pytest.approx(0.109, abs=0.001)
    assert 3 * head / total == pytest.approx(0.123, abs=0.001)
    # q, k, v (bf16), g and beta (f32) read, o written; backward reads
    # them and o's gradient and writes theirs
    inputs = 3 * 4096 * 2 + (4096 + 32) * 4
    assert kimi_linear_flops.rule_train_bytes_per_token(config) \
        == (inputs + 8192) + (inputs + 8192 + inputs) == 139648
    # the rule's floor is its bytes: 170 ns a token a layer against 48
    assert 139648 / 819e9 > 3 * rule / 197e12


def test_rehearsal_flops_against_a_hand_count():
    config = load("configs", CONFIG, True)
    assert kimi_linear_flops.kda_sizes(config) == (4, 16, 64, 4)
    rule = 3 * 2 * 4 * 16 * 16
    kda = 2 * (64 * (3 * 64 + 2 * 16 + 4) + 2 * 16 * 64) \
        + 3 * 2 * 4 * 64 + rule + 2 * 64 * 64
    mla = 2 * (64 * 4 * 32 + 64 * 48 + 32 * 4 * 32 + 4 * 16 * 64)
    attended = 3 * 2 * 4 * (32 + 16) * 64.5
    expert_layer = 2 * (64 * 16 + 3 * 64 * 32 * (1 + 4 * 4 / 16))
    total = 3 * (4 * kda + mla + 2 * 3 * 64 * 96 + 4 * expert_layer
                 + 2 * 256 * 64) + attended
    assert kimi_linear_flops.train_flops_per_token(config, 128) == total


def test_parameter_count_is_the_configurations_and_the_programs():
    config = load("configs", CONFIG, False)
    from chipbench.adapters import kimi_linear_train as adapter
    from chipbench.references import kimi_linear_train as reference

    def count(shapes, mark=""):
        return sum(int(jnp.prod(jnp.asarray(shape)))
                   for leaf, shape in shapes.items() if mark in leaf)

    shapes = weights.shapes(reference.param_spec(config))
    program, _ = adapter.param_shapes(config, load("workloads", MIX, False))
    assert shapes == weights.shapes(program)
    assert count(shapes) == config["parameters"] == 602433408   # 9.64 GB
    assert count(shapes, "['kda']") == 4 * 39514272
    assert count(shapes, "['attn']") == 29114880
    assert count(shapes, "['moe']") == 4 * 64290816
    assert count(shapes, "['mlp']") == 63700992
    for number in ("39,514,272", "29,114,880", "64,290,816", "602,433,408"):
        assert number in config["cut_to_size"], number
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "num_experts", "vocab_size"]
    assert set(config["published"]) == set(config["reduced"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["num_experts"] * 32 == config["published"]["num_experts"]
    assert config["deployment"]["chips_that_share_a_layer"] \
        == config["deployment"]["expert_parallel"] == 32
    for key in ("published", "assumed", "departures", "stands_for",
                "cut_to_size"):
        assert config[key]


def test_the_cell_reports_what_its_scopes_have():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert len(bench["workloads"]) == 12
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    assert bench["workloads"][-1] is cell and bench["configs"][-1]["name"] \
        == CONFIG
    names = {m["name"] for m in metrics_of(bench, "per_layer", CELL)}
    assert set(NEW) | {
        "mlp_ms_per_step", "attention_ms_per_step", "loss_head_ms_per_step",
        "flash_fwd_ms_per_step", "flash_dkv_ms_per_step", "moe_ms_per_step",
        "moe_held_assignments_per_token", "moe_dropped_assignments",
        "scope_unattributed_pct"} <= names
    assert not {"flash_roofline", "ssm_ms_per_step",
                "attention_full_ms_per_step"} & names
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == list(NEW)
    for metric in bench["per_layer"][-len(NEW):]:
        assert metric["workloads"] == [CELL] \
            and metric["moves"] == "tokens_per_s_per_chip"
    assert {m["name"] for m in metrics_of(bench, "end_to_end", CELL)} == {
        "tokens_per_s_per_chip", "mfu_pct", "step_ms_p90", "setup_s"}


def test_the_mix_is_s8k_1chip_settleds():
    """Every key but ``why`` equal to ``s8k-1chip-settled``'s: the ids
    come from the configuration's slice of the vocabulary."""
    with open(os.path.join(HERE, "..", "workloads",
                           "s8k-1chip-settled.json")) as f:
        settled = json.load(f)
    with open(os.path.join(HERE, "..", "workloads", MIX + ".json")) as f:
        mix = json.load(f)
    assert mix.pop("why") != settled.pop("why")
    assert mix == settled and mix["warmup_steps"] <= 200


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_no_width_differs_from_the_catalogs_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    config = load("configs", CONFIG, False)
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    # the nested group whole, its two lists as published: the layers run
    # are the top-level ``layer_types``, published layer 1 and layers 5-8
    linear = config["linear_attn_config"]
    kinds = ["mla" if n in linear["full_attn_layers"] else "kda"
             for n in (1, 5, 6, 7, 8)]
    assert config["layer_types"] == kinds \
        == ["kda", "kda", "kda", "kda", "mla"]
    assert sorted(linear["full_attn_layers"] + linear["kda_layers"]) \
        == list(range(1, 28))
    assert config["num_experts_per_tok"] == config["num_experts_per_token"]


# ---------------------------------------------------------------------------
# the reference against the program

def case():
    from chipbench.adapters import kimi_linear_train as adapter
    from chipbench.inputs import tokens
    from chipbench.references import kimi_linear_train as reference

    return (load("configs", CONFIG, True), load("workloads", MIX, True),
            adapter, reference, tokens.make)


def test_reference_tree_is_the_programs():
    from chipbench.adapters import kimi_linear_train as adapter
    from chipbench.references import kimi_linear_train as reference

    for rehearse in (True, False):
        config = load("configs", CONFIG, rehearse)
        workload = load("workloads", MIX, rehearse)
        params, aux = adapter.param_shapes(config, workload)
        assert weights.shapes(reference.param_spec(config)) == \
            weights.shapes(params)
        assert weights.shapes(reference.aux_spec(config)) == \
            weights.shapes(aux)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(HERE, "..", "references",
                           "kimi_linear_train.py")) as f:
        source = f.read()
    assert "horovod_tpu" not in source.split('"""', 2)[2]
    assert "lax.scan(position" in source        # the recurrence itself


def test_loss_and_gradient_match_the_program_in_float32():
    """The program's model in float32 with its dense attention inner and
    its chunked rule (four chunks a row) is the reference, whose rule is
    the recurrence, to rounding: both kinds of layer, the routed experts
    held here, the untied head over the slice."""
    from horovod_tpu.models import TransformerLM, make_fused_lm_loss

    from chipbench.references import precision

    config, workload, adapter, reference, make = case()
    key = weights.seed_key(2**31 + 5)
    batch = make(jax.random.fold_in(key, 1), config, workload, 2)
    params = weights.make(key, reference.param_spec(config))
    aux = weights.make(key, reference.aux_spec(config))
    einsum, _ = precision.products("float32")
    (want, _), want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.batch_loss(config, einsum, p, batch, aux),
        has_aux=True))(params)
    assert abs(float(want) - reference.first_loss(config)) < 0.05
    cfg = dataclasses.replace(adapter.program_config(config, workload),
                              dtype=jnp.float32)
    assert cfg.kda_chunk_size * 4 == workload["seq_len"]
    loss_fn = make_fused_lm_loss(TransformerLM(cfg), n_chunks=4,
                                 with_state=True)
    (got, _), got_grads = jax.jit(jax.value_and_grad(
        loss_fn, has_aux=True))(params, aux, batch)
    assert abs(float(got) - float(want)) < 2e-5
    norms, want_norms = weights.leaf_norms(got_grads), \
        weights.leaf_norms(want_grads)
    for leaf, value in want_norms.items():
        assert float(norms[leaf]) == pytest.approx(float(value), rel=2e-3,
                                                   abs=1e-7), leaf


def test_fp8_control_fails_the_rehearsal_limits():
    config, workload, _, reference, make = case()
    with open(os.path.join(HERE, "..", "limits", CELL + ".json")) as f:
        limits = json.load(f)["rehearsal"]
    for seed in (5, 2**31 + 7):
        key = weights.seed_key(seed)
        batch = make(jax.random.fold_in(key, 1), config, workload, 2)
        sound = reference.follow(config, workload, key, batch, 2)
        control = reference.follow(config, workload, key, batch, 2, "fp8")
        over = [name for name, value, limit, _ in
                gaps(control, sound, limits) if value > limit]
        assert over, seed


def test_the_cell_rehearses_end_to_end():
    """``--rehearse 1``: the cell's files found by name, the reference,
    the compiled step with interpret-mode kernels, the comparison, a
    window and a traced step, on the CPU."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "1",
         "--trace", "1", "--rehearse", "1"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True, \
        done.stderr[-2000:]
    assert line["failed"] == 0 and line["attempted"] >= 1


# ---------------------------------------------------------------------------
# the new readers, on the scope table recorded on the chip and on a table,
# intervals and counters made by hand

STEP = "jit(prog)/hvd_step/loss_and_grad/"
FWD = STEP + "vmap(jvp(TransformerLM))/TransformerLM._layered/while/body/" \
    "closed_call/periods/checkpoint/layer_0/"
BWD = STEP + "vmap(transpose(jvp(TransformerLM)))/TransformerLM._layered/" \
    "while/body/closed_call/periods/periods/checkpoint/layer_0/"
KERNEL = " = custom-call bf16[8]" + tr.KERNEL_MARK
MLA = FWD.replace("layer_0", "layer_3") + "mla/attn/"
TABLE = {
    "fusion.1": FWD + "kda/in_proj/wq/dot_general",
    "fusion.2": FWD + "kda/conv/conv_q/mul",
    "fusion.3": FWD + "kda/delta/while/body/cumsum",
    "fusion.4": FWD + "kda/delta/while/body/...ic,...jc->...ij/dot_general",
    "fusion.5": FWD + "kda/delta/while/body/while/body/mul",
    "fusion.6": FWD + "kda/gate_norm/rsqrt",
    "fusion.7": FWD + "kda/out_proj/dot_general",
    "fusion.8": FWD + "moe/shared/wi_gate/dot_general",
    "attn.9": MLA + "flash_fwd/flash_fwd",
    "fusion.10": BWD + "rematted_computation/kda/conv/conv_k/mul",
    "fusion.11": BWD + "kda/transpose(jvp(delta))/while/body/dot_general",
    "fusion.12": BWD + "kda/transpose(jvp(conv))/conv_v/mul",
    "fusion.13": "jit(prog)/vmap(jvp(TransformerLM))/kda/delta/mul",
    "fusion.14": MLA + "kv_b/dot_general",
    "attn.15": BWD.replace("layer_0", "layer_3")
    + "mla/attn/flash_dkv/flash_dkv",
}


def traced(trace_steps=2):
    rows = []
    for step in range(trace_steps):
        for i, name in enumerate(list(TABLE) + ["unknown.99"]):
            mark = KERNEL if name.startswith("attn.") else " = fusion f32[4]"
            start = (step * 20 + i) * 1e-3
            rows.append(Op(0, tr.OPS_LINE, name + mark, start, start + 1e-3))
    return rows


NAMES = ("horovod_kda_tokens_total", "horovod_kda_chunks_total")


def context(tokens=0.0, chunks=0.0):
    from chipbench.adapters import kimi_linear_train as adapter

    ctx = {"trace": traced(), "trace_steps": 2, "ranks": 1,
           "adapter": adapter,
           "config": load("configs", CONFIG, False),
           "workload": load("workloads", MIX, False),
           "peaks": flops.peaks("TPU v5 lite"),
           "window": {"steps": 30, "samples_per_step": 16384},
           "_program_report": {"scopes": TABLE, "module": "jit_prog"}}
    ctx["counters"] = {
        "window_start": dict.fromkeys(NAMES, 7.0),
        "window_end": dict(zip(NAMES, (7.0 + tokens, 7.0 + chunks)))}
    return ctx


def test_time_under_the_mixers_the_rule_and_the_convolutions():
    """1 ms an operation: eleven of the fifteen under ``kda`` and a step
    scope, four of them under ``delta``, three under ``conv``; three
    under ``mla``, two of them flash kernels; the one without a step
    scope is not booked, and the accepted readers keep the delta rule
    out of ``attention`` and book latent attention to it."""
    ctx = context()
    assert reader("kda_ms_per_step").read(ctx) == pytest.approx(10.0)
    assert reader("kda_scan_ms_per_step").read(ctx) == pytest.approx(4.0)
    assert reader("kda_conv_ms_per_step").read(ctx) == pytest.approx(3.0)
    assert reader("mla_ms_per_step").read(ctx) == pytest.approx(3.0)
    assert reader("attention_ms_per_step").read(ctx) == pytest.approx(3.0)
    assert reader("flash_fwd_ms_per_step").read(ctx) == pytest.approx(1.0)
    assert reader("flash_dkv_ms_per_step").read(ctx) == pytest.approx(1.0)
    assert reader("moe_ms_per_step").read(ctx) == pytest.approx(1.0)


def test_the_rules_share_of_its_roofline_and_the_chunk_that_ran():
    """30 steps of 4 layers x 16,384 tokens in chunks of 64: the floor
    is the bytes', 65,536 x 139,648 / 819e9 = 11.17 ms, over the 4 ms
    under ``delta`` would read 279%: an error of the count, refused.
    Over 40 ms it reads 27.9%."""
    tokens = 30 * 4 * 16384
    ctx = context(tokens, tokens / 64)
    assert reader("kda_tokens_per_chunk").read(ctx) == 64
    floor_s = 4 * 16384 * 139648 / 819e9
    with pytest.raises(ValueError, match="kda_scan_roofline reads 279"):
        reader("kda_scan_roofline").read(ctx)
    slow = context(tokens, tokens / 64)
    slow["trace"] = [op._replace(end=op.start + 1e-2) for op in traced()]
    assert reader("kda_scan_roofline").read(slow) == pytest.approx(
        100 * floor_s / 4e-2)
    two_ranks = dict(slow, ranks=2, counters={
        "window_start": dict.fromkeys(NAMES, 0.0),
        "window_end": dict(zip(NAMES, (2.0 * tokens, 2.0 * tokens / 64)))})
    assert reader("kda_scan_roofline").read(two_ranks) == pytest.approx(
        100 * floor_s / 4e-2)


def test_the_latent_layers_flash_kernels_against_the_published_flops():
    """16,384 tokens x 251,688,960 FLOPs = 4.124 TFLOP a step at the
    published 192 / 128 widths, 20.93 ms at the peak: over the 2 ms of
    the two kernels under ``mla`` it would read over 105% and is
    refused; over 40 ms it reads 52.3%."""
    ctx = context()
    floor_s = 16384 * 251688960 / 197e12
    with pytest.raises(ValueError, match="mla_flash_roofline reads"):
        reader("mla_flash_roofline").read(ctx)
    slow = context()
    slow["trace"] = [op._replace(end=op.start + 2e-2) for op in traced()]
    assert reader("mla_flash_roofline").read(slow) == pytest.approx(
        100 * floor_s / 4e-2)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_a_program_without_the_names(name):
    """The parent commit: no report, unknown counters read 0; a run
    without a trace; and Granite's step, which has neither scope."""
    tokens = 30 * 4 * 16384
    bare = context(tokens, tokens / 64)
    bare["_program_report"] = None
    bare["counters"]["window_end"] = dict.fromkeys(NAMES, 7.0)
    assert reader(name).read(bare) is None
    if name != "kda_tokens_per_chunk":
        assert reader(name).read(dict(context(tokens, tokens / 64),
                                      trace=None)) is None
    granite = context()
    granite["_program_report"] = {
        "scopes": {k: v.replace("/kda/", "/mamba/").replace("/mla/", "/")
                   for k, v in TABLE.items()}, "module": "jit_prog"}
    assert reader(name).read(granite) is None


def recorded():
    with open(os.path.join(HERE, "data_scopes", CELL + ".json")) as f:
        return json.load(f)


@pytest.mark.skipif(
    not os.path.exists(os.path.join(HERE, "data_scopes", CELL + ".json")),
    reason="no recording of the cell's traced step")
def test_new_readers_on_the_scope_table_recorded_on_the_chip():
    """The first traced step of the cell on the chip: the device events
    under ``kda`` or ``mla`` with the step program's own table for
    them; each new reader reads what the recording's ``expect`` holds
    (computed once, when the recording was made, from the same
    events)."""
    from chipbench.adapters import kimi_linear_train as adapter

    data = recorded()
    ops = [Op(*event) for event in (
        [d, line, name, start * 1e-9, end * 1e-9]
        for d, line, name, start, end in data["events"])]
    steps = data["window_steps"]
    ctx = {"trace": ops, "trace_steps": 1, "ranks": 1, "adapter": adapter,
           "config": load("configs", CONFIG, False),
           "workload": load("workloads", MIX, False),
           "peaks": flops.peaks("TPU v5 lite"),
           "window": {"steps": steps, "samples_per_step": 16384},
           "_program_report": {"scopes": data["scopes"],
                               "module": data["module"]},
           "counters": {
               "window_start": dict.fromkeys(NAMES, 0.0),
               "window_end": dict(zip(NAMES, (
                   steps * 4 * 16384.0, steps * 4 * 16384.0 / 64)))}}
    for name in NEW:
        assert reader(name).read(ctx) == pytest.approx(
            data["expect"][name], rel=1e-6), name
    assert data["expect"]["kda_scan_ms_per_step"] \
        < data["expect"]["kda_ms_per_step"]
    assert 0 < data["expect"]["kda_scan_roofline"] < 105
    assert 0 < data["expect"]["mla_flash_roofline"] < 105
