"""What the ``granite_hybrid_train`` configuration (Granite-4.0-H Micro)
brings: its parameter count, its widths against the catalog's row, its
FLOP and byte counts against hand-worked ones, its mix against
``s8k-1chip``, its plain reference against the program at the rehearsal
sizes, the fp8 control failing the rehearsal's limits, and each new
reader on a table, intervals and counters made by hand.  (The rehearsal
of the new cell is ``test_run.py``'s.)"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import flops, ssm_flops, weights
from chipbench import trace_reduce as tr
from chipbench.run import gaps, metrics_of, with_rehearsal
from chipbench.trace_reduce import Op

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "granite-4.0-h-micro-s8k-1chip"
CONFIG = "granite-4.0-h-micro-l10"
MIX = "s8k-b1-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
    """One period as the cell runs it, at 8,192 tokens a row."""
    config = load("configs", CONFIG, False)
    assert ssm_flops.mamba_sizes(config) == (64, 64, 1, 128, 4096, 4352)
    # the scan at the published chunk of 256: a mean position reads
    # 128.5 sources of its chunk through C B^T (128 wide) and through the
    # masked product (4096 wide); the state's two products 128 x 4096
    within = 2 * 128 * 128.5 + 2 * 4096 * 128.5
    across = 2 * 2 * 128 * 4096
    assert ssm_flops.scan_flops_per_token(config, 256) == within + across \
        == 3182720
    mixer = 2 * 2048 * 8512 + 2 * 4 * 4352 + 3182720 + 2 * 4096 * 2048
    assert ssm_flops.mamba_layer_flops_per_token(config) == mixer \
        == 54859904
    projections = 2 * 2048 * 64 * (32 + 16) + 2 * 2048 * 2048
    assert ssm_flops.attention_projection_flops_per_token(config) \
        == projections == 20971520
    mlp = 3 * 2 * 2048 * 8192
    # the one attention layer's query sees 4,096.5 keys on average
    attended = 3 * 2 * 2 * 32 * 64 * 4096.5
    assert ssm_flops.attention_train_flops_per_token(config, 8192) \
        == attended == 100675584
    head = 2 * 25088 * 2048
    total = ssm_flops.train_flops_per_token(config, 8192)
    assert total == 3 * (9 * mixer + projections + 10 * mlp + head) \
        + attended == 4972987776
    # by FLOPs: MLP 61%, the mixers' projections 28%, the head 6%, the
    # scans' own products 1.7%, the causal 8k block 2%
    assert 3 * 10 * mlp / total == pytest.approx(0.607, abs=0.001)
    assert 3 * head / total == pytest.approx(0.062, abs=0.001)
    assert 3 * 9 * 3182720 / total == pytest.approx(0.0173, abs=0.0005)
    assert attended / total == pytest.approx(0.0202, abs=0.0005)
    # a shorter chunk needs fewer operations inside a chunk
    assert ssm_flops.scan_train_flops_per_token(config, 128) \
        == 3 * (2 * (128 + 4096) * 64.5 + across)
    # x, B, C (bf16) and dt (f32) read, y written; backward reads them
    # and y's gradient and writes theirs
    inputs = (4096 + 256) * 2 + 64 * 4
    assert ssm_flops.scan_train_bytes_per_token(config) \
        == (inputs + 8192) + (inputs + 8192 + inputs) == 43264


def test_parameter_count_is_the_configurations():
    config = load("configs", CONFIG, False)
    from chipbench.references import granite_hybrid_train as reference

    shapes = weights.shapes(reference.param_spec(config))
    n = sum(int(jnp.prod(jnp.asarray(shape))) for shape in shapes.values())
    assert n == config["parameters"] == 797850560    # 12.77 GB at 16 bytes
    mamba = sum(int(jnp.prod(jnp.asarray(shape)))
                for leaf, shape in shapes.items() if "['layer_0']" in leaf)
    attention = sum(int(jnp.prod(jnp.asarray(shape)))
                    for leaf, shape in shapes.items() if "['layer_5']" in leaf)
    assert (mamba, attention) == (76182976, 60821504)
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "vocab_size"]
    assert set(config["published"]) == set(config["reduced"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert config["vocab_size"] * 4 == config["published"]["vocab_size"]
    assert config["deployment"]["pipeline_stages"] == 4
    for key in ("published", "assumed", "departures", "stands_for"):
        assert config[key]


def test_the_cell_reports_what_its_scopes_have():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    names = {m["name"] for m in metrics_of(bench, "per_layer", CELL)}
    assert {"ssm_ms_per_step", "ssm_scan_ms_per_step", "ssm_conv_ms_per_step",
            "ssm_scan_roofline", "mlp_ms_per_step", "attention_ms_per_step",
            "attention_full_ms_per_step", "loss_head_ms_per_step", "flash_fwd_ms_per_step",
            "flash_dkv_ms_per_step", "flash_scoped_roofline",
            "scope_unattributed_pct"} <= names
    assert "flash_roofline" not in names
    assert {m["name"] for m in metrics_of(bench, "end_to_end", CELL)} == {
        "tokens_per_s_per_chip", "mfu_pct", "step_ms_p90", "setup_s"}


def test_the_mix_is_s8k_1chips_with_one_row():
    """ISSUE 39: every key but ``batch``, the rehearsal's ``batch`` and
    ``why`` equal to ``s8k-1chip``'s."""
    with open(os.path.join(HERE, "..", "workloads", "s8k-1chip.json")) as f:
        s8k = json.load(f)
    with open(os.path.join(HERE, "..", "workloads", MIX + ".json")) as f:
        mix = json.load(f)
    assert mix.pop("batch") == 1 and s8k.pop("batch") == 2
    assert mix["rehearsal"].pop("batch") == 1 \
        and s8k["rehearsal"].pop("batch") == 2
    assert mix.pop("why") != s8k.pop("why")
    assert mix == s8k


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_no_width_differs_from_the_catalogs_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    config = load("configs", CONFIG, False)
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            want = config["published"][key]
            assert want == value or isinstance(want, str), key
            if isinstance(value, list):     # the pattern: its first period
                assert config[key] == value[:len(config[key])]
        else:
            assert config[key] == value, key
    assert config["layer_types"].count("attention") == 1 \
        and config["layer_types"][5] == "attention"


# ---------------------------------------------------------------------------
# the reference against the program

def case():
    from chipbench.adapters import granite_hybrid_train as adapter
    from chipbench.inputs import tokens
    from chipbench.references import granite_hybrid_train as reference

    return (load("configs", CONFIG, True), load("workloads", MIX, True),
            adapter, reference, tokens.make)


def test_reference_tree_is_the_programs():
    from chipbench.adapters import granite_hybrid_train as adapter
    from chipbench.references import granite_hybrid_train as reference

    for rehearse in (True, False):
        config = load("configs", CONFIG, rehearse)
        workload = load("workloads", MIX, rehearse)
        params, aux = adapter.param_shapes(config, workload)
        assert weights.shapes(reference.param_spec(config)) == \
            weights.shapes(params)
        assert aux is None and reference.aux_spec(config) is None


def test_loss_and_gradient_match_the_program_in_float32():
    """The program's model in float32 with its dense attention and its
    chunked scan (four chunks a row) is the reference, whose scan is the
    recurrence, to rounding: both kinds of layer, the four multipliers,
    the tied head over the slice."""
    from horovod_tpu.models import TransformerLM, make_fused_lm_loss

    from chipbench.references import precision

    config, workload, adapter, reference, make = case()
    key = weights.seed_key(2**31 + 5)
    batch = make(jax.random.fold_in(key, 1), config, workload, 2)
    params = weights.make(key, reference.param_spec(config))
    einsum, _ = precision.products("float32")
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.batch_loss(config, einsum, p, batch)))(params)
    assert abs(float(want) - reference.first_loss(config)) < 0.05
    cfg = dataclasses.replace(adapter.program_config(config, workload),
                              dtype=jnp.float32)
    assert cfg.mamba_chunk_size * 4 == workload["seq_len"]
    loss_fn = make_fused_lm_loss(TransformerLM(cfg), n_chunks=4)
    got, got_grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
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
        batch = make(jax.random.fold_in(key, 1), config, workload, 1)
        sound = reference.follow(config, workload, key, batch, 2)
        control = reference.follow(config, workload, key, batch, 2, "fp8")
        over = [name for name, value, limit, _ in
                gaps(control, sound, limits) if value > limit]
        assert over, seed


# ---------------------------------------------------------------------------
# the new readers, on a table, intervals and counters made by hand (the
# paths as the described-v5e compile of the cell's step writes them)

STEP = "jit(prog)/hvd_step/loss_and_grad/"
FWD = STEP + "vmap(jvp(TransformerLM))/TransformerLM._layered/while/body/" \
    "closed_call/periods/checkpoint/layer_0/"
BWD = STEP + "vmap(transpose(jvp(TransformerLM)))/TransformerLM._layered/" \
    "while/body/closed_call/periods/periods/checkpoint/layer_0/"
KERNEL = " = custom-call bf16[8]" + tr.KERNEL_MARK
TABLE = {
    "fusion.1": FWD + "mamba/in_proj/dot_general",
    "fusion.2": FWD + "mamba/conv/mul",
    "fusion.3": FWD + "mamba/ssd/cumsum",
    "fusion.4": FWD + "mamba/ssd/bcgrls,bcsgrp->bclgrp/dot_general",
    "fusion.5": FWD + "mamba/ssd/while/body/mul",
    "fusion.6": FWD + "mamba/gate_norm/rsqrt",
    "fusion.7": FWD + "mamba/out_proj/dot_general",
    "fusion.8": FWD + "mlp/wi_gate/dot_general",
    "attn.9": FWD.replace("layer_0", "layer_5")
    + "full_attention/attn/flash_fwd/flash_fwd",
    "fusion.10": BWD + "rematted_computation/mamba/ssd/exp",
    "fusion.11": BWD + "mamba/transpose(jvp(ssd))/dot_general",
    "fusion.12": BWD + "mamba/transpose(jvp(conv))/mul",
    "fusion.13": "jit(prog)/vmap(jvp(TransformerLM))/mamba/ssd/mul",
}


def traced(trace_steps=2):
    rows = []
    for step in range(trace_steps):
        for i, name in enumerate(list(TABLE) + ["unknown.99"]):
            mark = KERNEL if name.startswith("attn.") else " = fusion f32[4]"
            start = (step * 20 + i) * 1e-3
            rows.append(Op(0, tr.OPS_LINE, name + mark, start, start + 1e-3))
    return rows


NAMES = ("horovod_ssm_tokens_total", "horovod_ssm_chunks_total")


def context(tokens=0.0, chunks=0.0):
    ctx = {"trace": traced(), "trace_steps": 2, "ranks": 1,
           "config": load("configs", CONFIG, False),
           "workload": load("workloads", MIX, False),
           "peaks": flops.peaks("TPU v5 lite"),
           "window": {"steps": 30, "samples_per_step": 8192},
           "_program_report": {"scopes": TABLE, "module": "jit_prog"}}
    ctx["counters"] = {
        "window_start": dict.fromkeys(NAMES, 7.0),
        "window_end": dict(zip(NAMES, (7.0 + tokens, 7.0 + chunks)))}
    return ctx


def test_time_under_the_mixer_its_scan_and_its_convolution():
    """1 ms an operation: ten of the thirteen under ``mamba`` and a step
    scope, five of them under ``ssd``, two under ``conv``; the one
    without a step scope is not booked, and the accepted readers keep
    the mixer out of ``attention`` and the MLP in ``mlp``."""
    ctx = context()
    assert reader("ssm_ms_per_step").read(ctx) == pytest.approx(10.0)
    assert reader("ssm_scan_ms_per_step").read(ctx) == pytest.approx(5.0)
    assert reader("ssm_conv_ms_per_step").read(ctx) == pytest.approx(2.0)
    assert reader("attention_ms_per_step").read(ctx) == pytest.approx(1.0)
    # the one attention layer is of the kind full_attention
    assert reader("attention_full_ms_per_step").read(ctx) \
        == pytest.approx(1.0)
    assert reader("mlp_ms_per_step").read(ctx) == pytest.approx(1.0)


def test_the_scans_share_of_their_roofline():
    """30 steps of 9 layers x 8,192 tokens in chunks of 256: the floor
    is the bytes', 73,728 x 43,264 / 819e9 = 3.895 ms, over the 5 ms
    under ``ssd``; at chunks of 16,384 positions the products' FLOPs
    would bound it."""
    tokens = 30 * 9 * 8192
    ctx = context(tokens, tokens / 256)
    bytes_s = 9 * 8192 * 43264 / 819e9
    flops_s = 9 * 8192 * 3 * 3182720 / 197e12
    assert bytes_s > flops_s
    assert reader("ssm_scan_roofline").read(ctx) == pytest.approx(
        100 * bytes_s / 5e-3)
    long = context(tokens, tokens / 16384)
    assert reader("ssm_scan_roofline").read(long) == pytest.approx(
        100 * 9 * 8192 * ssm_flops.scan_train_flops_per_token(
            long["config"], 16384) / 197e12 / 5e-3)
    two_ranks = dict(context(2 * tokens, 2 * tokens / 256), ranks=2)
    assert reader("ssm_scan_roofline").read(two_ranks) == pytest.approx(
        100 * bytes_s / 5e-3)


@pytest.mark.parametrize("name", [
    "ssm_ms_per_step", "ssm_scan_ms_per_step", "ssm_conv_ms_per_step",
    "ssm_scan_roofline"])
def test_new_readers_find_nothing_in_a_program_without_the_names(name):
    """The parent commit: no report, unknown counters read 0; a run
    without a trace; and Mistral's step, which has no ``mamba``."""
    tokens = 30 * 9 * 8192
    bare = context(tokens, tokens / 256)
    bare["_program_report"] = None
    assert reader(name).read(bare) is None
    assert reader(name).read(dict(context(tokens, tokens / 256),
                                  trace=None)) is None
    mistral = context()
    mistral["_program_report"] = {
        "scopes": {k: v.replace("/mamba/", "/attn/")
                   for k, v in TABLE.items()}, "module": "jit_prog"}
    assert reader(name).read(mistral) is None
    if name == "ssm_scan_roofline":
        # the scopes without the counters (they read 0 on the parent)
        assert reader(name).read(context()) is None
        assert reader(name).COUNTERS == list(NAMES)
