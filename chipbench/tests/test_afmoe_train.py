"""What the ``afmoe_train`` configuration (Trinity-Mini) brings: its FLOP
count against a hand-worked one, its plain reference against the program
at the rehearsal sizes, the fp8 control failing the rehearsal's limits,
and each new reader on a table, intervals and counters made by hand.
(The rehearsal of both new cells is ``test_run.py``'s.)"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import afmoe_flops, flops, scope_time, weights
from chipbench import trace_reduce as tr
from chipbench.run import gaps, with_rehearsal
from chipbench.trace_reduce import Op

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "trinity-mini-s8k-1chip"


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
# operations

def test_flops_per_token_against_a_hand_worked_count():
    """Trinity-Mini's share as the cell runs it, at 8,192 tokens a row."""
    config = load("configs", "trinity-mini-l5-ep8", False)
    attention = 2048 * 4096 * 2 + 2048 * 512 * 2 + 4096 * 2048   # q g k v o
    assert afmoe_flops.attention_matmul_params(config) == attention \
        == 27262976
    expert = 3 * 2048 * 1024
    assert afmoe_flops.held_assignments_per_token(config) == 1.0
    matrices = 5 * attention + 3 * 2048 * 6144 \
        + 4 * (2048 * 128 + expert + expert) + 25024 * 2048
    assert afmoe_flops.matmul_params_per_token(config) == matrices \
        == 276692992
    # a sliding layer's query sees 1,792.125 keys on average, the full
    # layer's 4,096.5
    assert afmoe_flops.keys_attended(config, 8192) == [
        1792.125, 1792.125, 1792.125, 4096.5, 1792.125]
    attended = 6 * 2 * 32 * 128 * (4 * 1792.125 + 4096.5)
    assert afmoe_flops.attention_train_flops_per_token(config, 8192) \
        == attended == 553697280
    total = afmoe_flops.train_flops_per_token(config, 8192)
    assert total == 6 * matrices + attended == 2213855232
    assert attended / total == pytest.approx(0.25, abs=0.002)
    assert afmoe_flops.grouped_products_train_flops_per_assignment(config) \
        == 18 * 2048 * 1024
    assert flops.mean_keys_attended(8192, None) == 4096.5


def test_parameter_count_is_the_configurations():
    config = load("configs", "trinity-mini-l5-ep8", False)
    from chipbench.references import afmoe_train as reference

    n = sum(int(jnp.prod(jnp.asarray(shape))) for shape in
            weights.shapes(reference.param_spec(config)).values())
    assert round(n / 1e6, 1) == 705.5           # 11.29 GB at 16 bytes
    assert set(config["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"}
    assert set(config["published"]) == set(config["reduced"])


# ---------------------------------------------------------------------------
# the reference against the program

def case():
    from chipbench.adapters import afmoe_train as adapter
    from chipbench.inputs import tokens
    from chipbench.references import afmoe_train as reference

    return (load("configs", "trinity-mini-l5-ep8", True),
            load("workloads", "s8k-1chip-settled", True), adapter, reference,
            tokens.make)


def test_reference_tree_is_the_programs():
    for rehearse in (True, False):
        config = load("configs", "trinity-mini-l5-ep8", rehearse)
        workload = load("workloads", "s8k-1chip-settled", rehearse)
        from chipbench.adapters import afmoe_train as adapter
        from chipbench.references import afmoe_train as reference

        params, aux = adapter.param_shapes(config, workload)
        assert weights.shapes(reference.param_spec(config)) == \
            weights.shapes(params)
        # every expert layer's expert_bias over all the router's experts
        assert weights.shapes(reference.aux_spec(config)) == \
            weights.shapes(aux)
        assert set(weights.shapes(aux).values()) == {
            (1, config["published"]["num_experts"])}


def test_loss_and_gradient_match_the_program_in_float32():
    """The program's model in float32 with its dense attention is the
    reference, to rounding: both kinds of layer, the head norms, the
    gate, the four norms, the router and the held experts' part."""
    from horovod_tpu.models import TransformerLM, make_fused_lm_loss

    from chipbench.references import precision

    config, workload, adapter, reference, make = case()
    key = weights.seed_key(2**31 + 5)
    batch = make(jax.random.fold_in(key, 1), config, workload, 2)
    params = weights.make(key, reference.param_spec(config))
    einsum, _ = precision.products("float32")
    want, want_grads = jax.value_and_grad(
        lambda p: reference.batch_loss(config, einsum, p, batch))(params)
    cfg = dataclasses.replace(adapter.program_config(config, workload),
                              dtype=jnp.float32)
    loss_fn = make_fused_lm_loss(TransformerLM(cfg), n_chunks=4)
    got, got_grads = jax.value_and_grad(loss_fn)(params, batch)
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


# ---------------------------------------------------------------------------
# the readers, on a table and intervals made by hand

STEP = "jit(prog)/hvd_step/loss_and_grad/"
FWD = STEP + "vmap(jvp(TransformerLM))/TransformerLM._layered/while/body/" \
    "closed_call/periods/layer_2/"
BWD = STEP + "vmap(transpose(jvp(TransformerLM)))/TransformerLM._layered/" \
    "while/body/closed_call/periods/periods/checkpoint/layer_2/"
KERNEL = " = custom-call bf16[8]" + tr.KERNEL_MARK
TABLE = {
    "fusion.1": FWD + "moe/route/dot_general",
    "sort.2": FWD + "moe/dispatch/sort",
    "ragged.3": FWD + "moe/while/body/closed_call/experts/ragged_dot",
    "scatter.4": FWD + "moe/while/body/closed_call/combine/scatter-add",
    "fusion.5": FWD + "moe/shared/wi_up/dot_general",
    "ragged.6": BWD + "moe/while/body/closed_call/"
    "transpose(jvp(experts))/ragged_dot",
    "gather.7": BWD + "moe/while/body/closed_call/"
    "transpose(jvp(combine))/gather",
    "fusion.8": BWD + "rematted_computation/moe/route/top_k",
    "attn.9": FWD + "full_attention/attn/flash_fwd/flash_fwd",
    "fusion.10": FWD + "full_attention/attn/wq/dot_general",
    "attn.11": FWD.replace("layer_2", "layer_1")
    + "sliding_attention/attn/flash_fwd/flash_fwd",
    "attn.12": BWD + "full_attention/attn/flash_dkv/flash_dkv",
    "fusion.13": FWD + "mlp/wo/dot_general",
    "fusion.14": "jit(prog)/vmap(jvp(TransformerLM))/moe/mul",  # no step scope
    # the compiler's own kernel for a grouped product: the program's
    # table recovers its path from its first stated user (PR 36) ...
    "ragged-dot-none.15": FWD + "moe/while/body/closed_call/ragged-dot-none",
    # ... and one with no such user keeps the compiler's string
    "ragged-dot-none.16": "ragged-dot-none",
}


def traced(trace_steps=2):
    """Every instruction of ``TABLE`` once a step for 1 ms on one chip,
    and one the table does not hold."""
    rows = []
    for step in range(trace_steps):
        for i, name in enumerate(list(TABLE) + ["unknown.99"]):
            mark = KERNEL if name.startswith(("attn.", "ragged")) \
                else " = fusion f32[4]"
            start = (step * 20 + i) * 1e-3
            rows.append(Op(0, tr.OPS_LINE, name + mark, start, start + 1e-3))
    return rows


def context(**more):
    config = load("configs", "trinity-mini-l5-ep8", False)
    ctx = {"trace": traced(), "trace_steps": 2, "ranks": 1, "config": config,
           "workload": load("workloads", "s8k-1chip-settled", False),
           "peaks": flops.peaks("TPU v5 lite"),
           "window": {"steps": 50, "samples_per_step": 16384},
           "_program_report": {
               "scopes": TABLE, "module": "jit_prog",
               "renamed": {k: "ragged-dot-none" for k in TABLE
                           if k.startswith("ragged-dot")}}}
    ctx.update(more)
    return ctx


def window(ctx, **deltas):
    names = {"assignments": "horovod_moe_assignments_total",
             "held": "horovod_moe_held_assignments_total",
             "dropped": "horovod_moe_dropped_assignments_total"}
    ctx["counters"] = {
        "window_start": {name: 7.0 for name in names.values()},
        "window_end": {names[k]: 7.0 + v for k, v in deltas.items()}}
    for name in names.values():
        ctx["counters"]["window_end"].setdefault(name, 7.0)
    return ctx


def test_component_patterns():
    import re

    under = re.compile(scope_time.under("moe", "experts"))
    assert under.search(TABLE["ragged.3"]) and under.search(TABLE["ragged.6"])
    assert not under.search(TABLE["fusion.1"])
    assert not re.search(scope_time.component("moe"), "a/moe_x/b")
    assert not re.search(scope_time.component("attn"),
                         "a/full_attention/b")


def test_time_under_the_moe_scopes():
    ctx = context()
    # eight of the instructions lie under ``moe`` with a step scope,
    # and the compiler's grouped-product kernel by its recovered path;
    # the one without a path is booked nowhere
    assert reader("moe_ms_per_step").read(ctx) == pytest.approx(9.0)
    # route x 2 (one recomputed), dispatch, combine x 2
    assert reader("moe_route_dispatch_ms_per_step").read(ctx) \
        == pytest.approx(5.0)
    assert reader("attention_full_ms_per_step").read(ctx) \
        == pytest.approx(3.0)


def test_experts_roofline_from_the_programs_count():
    """3 ms a step under ``experts`` for 65,536 held assignments a step
    (4 layers x 16,384), 18 x 2048 x 1024 FLOPs each (no chip does that:
    12.5 ms at the peak)."""
    ctx = window(context(), assignments=50 * 524288.0, held=50 * 65536.0)
    want = 100 * 65536 * 18 * 2048 * 1024 / 3e-3 / 197e12
    assert reader("moe_experts_roofline").read(ctx) == pytest.approx(want)
    assert reader("moe_held_assignments_per_token").read(ctx) \
        == pytest.approx(1.0)
    assert reader("moe_dropped_assignments").read(ctx) == 0.0
    dropped = window(context(), assignments=8.0, held=2.0, dropped=1.0)
    assert reader("moe_dropped_assignments").read(dropped) == 1.0


def test_flash_scoped_roofline_counts_the_scoped_kernels_only():
    """Three flash kernel calls of 1 ms a step (two forward, one
    backward); the grouped products are Pallas kernels too and are not
    counted."""
    from chipbench.adapters import afmoe_train as adapter

    ctx = context(adapter=adapter)
    need = 553697280 * 16384
    assert reader("flash_scoped_roofline").read(ctx) == pytest.approx(
        100 * need / 3e-3 / 197e12)


@pytest.mark.parametrize("name", [
    "moe_ms_per_step", "moe_route_dispatch_ms_per_step",
    "moe_experts_roofline", "moe_held_assignments_per_token",
    "moe_dropped_assignments", "attention_full_ms_per_step",
    "flash_scoped_roofline"])
def test_readers_find_nothing_in_a_program_without_the_names(name):
    """The parent commit: no report, unknown counters read 0; and a
    Mistral step: a report, no ``moe`` scope."""
    from chipbench.adapters import lm_train

    bare = window(context(adapter=lm_train, _program_report=None))
    assert reader(name).read(bare) is None
    no_trace = window(context(adapter=lm_train, trace=None))
    assert reader(name).read(no_trace) is None
    if name != "flash_scoped_roofline":
        table = {k: v.replace("/moe/", "/mlp/").replace(
            "full_attention/", "") for k, v in TABLE.items()
            if not k.startswith("ragged-dot")}
        mistral = window(context(_program_report={
            "scopes": table, "module": "jit_prog", "renamed": {}}))
        assert reader(name).read(mistral) is None


@pytest.mark.parametrize("cell, config, mix", [
    ("smallthinker-21b-s8k-1chip", "smallthinker-21b-l4-ep4",
     "s8k-1chip-settled-w200"),
    ("trinity-mini-s8k-1chip", "trinity-mini-l5-ep8", "s8k-1chip-settled")])
def test_moe_readers_read_a_recorded_step_as_the_parents_did(
        cell, config, mix):
    """One step of each routed cell as the chip traced it, with the
    program's own table (tests/data_scopes): the ``moe_*`` readers of
    the device trace read what the parent commit's read, which booked
    every ``ragged-dot*`` instruction under ``moe/experts`` by its name
    where these take the path the program recovered and, for the
    experts' time, the report's ``renamed``."""
    from chipbench.run import metrics_of

    with open(os.path.join(HERE, "data_scopes", cell + "-moe.json")) as f:
        data = json.load(f)
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = [Op(d, line, name, s * 1e-9, e * 1e-9)
              for d, line, name, s, e in data["events"]]
    held = 4 * 16384.0          # a step's held assignments, say
    ctx = window(context(
        trace=listed, trace_steps=1, config=load("configs", config, False),
        workload=load("workloads", mix, False),
        _program_report={"scopes": data["scopes"], "module": "jit_prog",
                         "renamed": data["renamed"]}), held=50 * held)
    expect = dict(data["expect"])
    experts_ms = expect.pop("moe_experts_roofline_ms")
    names = [name for name in expect if name in {
        m["name"] for m in metrics_of(bench, "per_layer", cell)}]
    assert len(names) >= 4 and "moe_ms_per_step" in names
    for name in names:
        assert reader(name).read(ctx) == pytest.approx(
            expect[name], rel=1e-12), name
    flops = afmoe_flops.grouped_products_train_flops_per_assignment(
        ctx["config"]) * held
    assert reader("moe_experts_roofline").read(ctx) == pytest.approx(
        100 * flops / (experts_ms / 1e3) / 197e12, rel=1e-12)
    # a report without the table of renamed kernels (before PR 36) has
    # no grouped product to its name: nothing to read, never a share of
    # the activations' time alone
    ctx = dict(ctx, _program_report={"scopes": data["scopes"],
                                     "module": "jit_prog"})
    assert reader("moe_experts_roofline").read(ctx) is None

