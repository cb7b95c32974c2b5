"""What the ``smallthinker_train`` configuration (SmallThinker) brings:
its FLOP count against a hand-worked one, the keys it repeats for the
shared readers equal to the published ones, its plain reference against
the program at the rehearsal sizes, the fp8 control failing the
rehearsal's limits, and each new reader on a table, intervals and
counters made by hand.  (The rehearsal of the new cell is
``test_run.py``'s.)"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import afmoe_flops, flops, smallthinker_flops, weights
from chipbench import trace_reduce as tr
from chipbench.run import gaps, with_rehearsal
from chipbench.trace_reduce import Op

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "smallthinker-21b-s8k-1chip"
CONFIG = "smallthinker-21b-l4-ep4"
MIX = "s8k-1chip-settled-w200"
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

def test_flops_per_token_against_a_hand_worked_count():
    """SmallThinker's share as the cell runs it, at 8,192 tokens a row."""
    config = load("configs", CONFIG, False)
    attention = 2560 * 3584 * 2 + 2560 * 512 * 2            # q o, k v
    assert smallthinker_flops.attention_matmul_params(config) == attention \
        == 20971520
    expert = 3 * 2560 * 768
    assert afmoe_flops.expert_matmul_params(config) == expert == 5898240
    assert afmoe_flops.held_assignments_per_token(config) == 1.5
    matrices = 4 * (attention + 2560 * 64 + 1.5 * expert) + 37984 * 2560
    assert smallthinker_flops.matmul_params_per_token(config) == matrices \
        == 217169920
    # the global layer's query sees 4,096.5 keys on average, a window
    # layer's 3,072.25 (the 4,096 window binds for half of the row)
    assert afmoe_flops.keys_attended(config, 8192) == [
        4096.5, 3072.25, 3072.25, 3072.25]
    attended = 6 * 2 * 28 * 128 * (4096.5 + 3 * 3072.25)
    assert smallthinker_flops.attention_train_flops_per_token(
        config, 8192) == attended == 572576256
    total = smallthinker_flops.train_flops_per_token(config, 8192)
    assert total == 6 * matrices + attended == 1875595776
    # by FLOPs: head 31%, QK^T / PV 30.5%, projections 27%, experts 11%
    assert 6 * 37984 * 2560 / total == pytest.approx(0.311, abs=0.001)
    assert attended / total == pytest.approx(0.305, abs=0.001)
    assert 6 * 4 * attention / total == pytest.approx(0.268, abs=0.001)
    assert 6 * 4 * 1.5 * expert / total == pytest.approx(0.113, abs=0.001)
    assert afmoe_flops.grouped_products_train_flops_per_assignment(config) \
        == 18 * 2560 * 768


def test_parameter_count_is_the_configurations():
    config = load("configs", CONFIG, False)
    from chipbench.references import smallthinker_train as reference

    n = sum(int(jnp.prod(jnp.asarray(shape))) for shape in
            weights.shapes(reference.param_spec(config)).values())
    assert n == 656529920                       # 10.50 GB at 16 bytes
    assert set(config["reduced"]) == {
        "num_hidden_layers", "sliding_window_layout", "rope_layout",
        "moe_num_primary_experts", "vocab_size"}
    assert set(config["published"]) == set(config["reduced"]) | {
        "num_experts"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[CONFIG]
    assert entry["reduced"] == config["reduced"]
    assert config["deployment"]["chips_that_share_a_layer"] == 4


@pytest.mark.parametrize("rehearse", [False, True])
def test_the_repeated_keys_equal_the_published_ones(rehearse):
    """The shared MoE readers and ``afmoe_flops`` know Trinity-Mini's
    names; the file repeats its own values under them."""
    config = load("configs", CONFIG, rehearse)
    assert config["num_experts_per_tok"] \
        == config["moe_num_active_primary_experts"]
    assert config["moe_intermediate_size"] == config["moe_ffn_hidden_size"]
    assert config["num_experts"] == config["moe_num_primary_experts"]
    assert config["published"]["num_experts"] \
        == config["published"]["moe_num_primary_experts"]
    assert config["sliding_window"] == config["sliding_window_size"]
    assert config["layer_types"] == [
        "sliding_attention" if w else "full_attention"
        for w in config["sliding_window_layout"]]
    assert config["rope_layout"] == config["sliding_window_layout"]
    assert config["num_shared_experts"] == 0
    # logits of std 2 over a layer-0 input of std 1 (``assumed``)
    assert config["router_initializer_std"] == pytest.approx(
        2 * config["hidden_size"] ** -0.5, rel=0.02)


def test_the_mix_is_the_settled_one_with_a_longer_warm_up():
    """ISSUE 34 step 4 (c): the cell's traffic is
    ``s8k-1chip-settled``'s in everything but the warm-up steps (and
    the line that says why)."""
    settled = load("workloads", "s8k-1chip-settled", False)
    mix = load("workloads", MIX, False)
    assert mix.pop("warmup_steps") == 200 > settled.pop("warmup_steps")
    assert mix.pop("why") != settled.pop("why")
    assert mix == settled


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_no_width_differs_from_the_catalogs_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    config = load("configs", CONFIG, False)
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            want = config["published"][key]
            assert want == value or isinstance(want, str), key
            if isinstance(value, list):     # a layout: its first entries
                assert config[key] == value[:len(config[key])]
        else:
            assert config[key] == value, key


# ---------------------------------------------------------------------------
# the reference against the program

def case():
    from chipbench.adapters import smallthinker_train as adapter
    from chipbench.inputs import tokens
    from chipbench.references import smallthinker_train as reference

    return (load("configs", CONFIG, True),
            load("workloads", MIX, True), adapter, reference,
            tokens.make)


def test_reference_tree_is_the_programs():
    from chipbench.adapters import smallthinker_train as adapter
    from chipbench.references import smallthinker_train as reference

    for rehearse in (True, False):
        config = load("configs", CONFIG, rehearse)
        workload = load("workloads", MIX, rehearse)
        params, aux = adapter.param_shapes(config, workload)
        assert weights.shapes(reference.param_spec(config)) == \
            weights.shapes(params)
        assert aux is None and reference.aux_spec(config) is None


def test_loss_and_gradient_match_the_program_in_float32():
    """The program's model in float32 with its dense attention is the
    reference, to rounding: both kinds of layer at 7 query heads a
    key/value head, the router on the layer's input, the ReGLU experts'
    held part, the balance term."""
    from horovod_tpu.models import TransformerLM, make_fused_lm_loss

    from chipbench.references import precision

    config, workload, adapter, reference, make = case()
    key = weights.seed_key(2**31 + 5)
    batch = make(jax.random.fold_in(key, 1), config, workload, 2)
    params = weights.make(key, reference.param_spec(config))
    einsum, _ = precision.products("float32")
    (want, seen), want_grads = jax.value_and_grad(
        lambda p: reference.batch_loss(config, einsum, p, batch),
        has_aux=True)(params)
    assert float(want) > float(seen["cross_entropy"]) \
        + config["router_aux_loss_coef"]
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
# the new readers, on a table, intervals and counters made by hand

STEP = "jit(prog)/hvd_step/loss_and_grad/"
FWD = STEP + "vmap(jvp(TransformerLM))/TransformerLM._layered/while/body/" \
    "closed_call/periods/layer_0/"
BWD = STEP + "vmap(transpose(jvp(TransformerLM)))/TransformerLM._layered/" \
    "while/body/closed_call/periods/periods/checkpoint/layer_0/"
KERNEL = " = custom-call bf16[8]" + tr.KERNEL_MARK
TABLE = {
    "fusion.1": FWD + "moe/route/dot_general",
    "fusion.2": FWD + "moe/route/top_k",
    "fusion.3": FWD + "moe/aux_loss/reduce_sum",
    "attn.4": FWD + "full_attention/attn/flash_fwd/flash_fwd",
    "sort.5": FWD + "moe/dispatch/sort",
    "ragged.6": FWD + "moe/while/body/closed_call/experts/ragged_dot",
    "fusion.7": BWD + "rematted_computation/moe/route/dot_general",
    "fusion.8": BWD + "moe/transpose(jvp(route))/dot_general",
    "fusion.9": BWD + "moe/transpose(jvp(aux_loss))/mul",
    "fusion.10": "jit(prog)/vmap(jvp(TransformerLM))/moe/route/mul",
}


def traced(trace_steps=2):
    rows = []
    for step in range(trace_steps):
        for i, name in enumerate(list(TABLE) + ["unknown.99"]):
            mark = KERNEL if name.startswith(("attn.", "ragged")) \
                else " = fusion f32[4]"
            start = (step * 20 + i) * 1e-3
            rows.append(Op(0, tr.OPS_LINE, name + mark, start, start + 1e-3))
    return rows


NAMES = {"aux": "horovod_moe_aux_loss_total",
         "busiest": "horovod_moe_max_expert_tokens_total"}


def context(**deltas):
    ctx = {"trace": traced(), "trace_steps": 2, "ranks": 1,
           "config": load("configs", CONFIG, False),
           "workload": load("workloads", MIX, False),
           "peaks": flops.peaks("TPU v5 lite"),
           "window": {"steps": 50, "samples_per_step": 16384},
           "_program_report": {"scopes": TABLE, "module": "jit_prog"}}
    ctx["counters"] = {
        "window_start": {name: 7.0 for name in NAMES.values()},
        "window_end": {name: 7.0 + deltas.get(k, 0.0)
                       for k, name in NAMES.items()}}
    return ctx


def test_time_under_route():
    """Forward product and top-k, the recomputed product and the
    product's transpose: four of the ten, 1 ms each; the one without a
    step scope is not booked."""
    ctx = context()
    assert reader("moe_route_ms_per_step").read(ctx) == pytest.approx(4.0)
    # the accepted readers hold the routing under ``moe`` where it was
    # issued, ahead of attention or not
    assert reader("moe_ms_per_step").read(ctx) == pytest.approx(8.0)
    assert reader("moe_route_dispatch_ms_per_step").read(ctx) \
        == pytest.approx(5.0)


def test_aux_loss_and_busiest_expert_from_the_programs_sums():
    """50 steps x 4 layers: a balance loss of 1.02 a layer, and a
    busiest expert with 1,920 tokens against the balanced 1,536."""
    ctx = context(aux=50 * 4 * 1.02, busiest=50 * 4 * 1920.0)
    assert reader("moe_router_aux_loss").read(ctx) == pytest.approx(1.02)
    assert reader("moe_max_expert_load").read(ctx) == pytest.approx(1.25)
    two_ranks = dict(ctx, ranks=2)
    two_ranks["window"] = {"steps": 50, "samples_per_step": 32768}
    assert reader("moe_router_aux_loss").read(two_ranks) \
        == pytest.approx(0.51)
    assert reader("moe_max_expert_load").read(two_ranks) \
        == pytest.approx(0.625)


@pytest.mark.parametrize("name", [
    "moe_route_ms_per_step", "moe_router_aux_loss", "moe_max_expert_load"])
def test_new_readers_find_nothing_in_a_program_without_the_names(name):
    """The parent commit: no report, unknown counters read 0; a run
    without a trace; and Trinity-Mini's step, which has ``moe/route``
    but no balance loss."""
    bare = context()
    bare["_program_report"] = None
    if name == "moe_route_ms_per_step":
        assert reader(name).read(bare) is None
        assert reader(name).read(dict(context(), trace=None)) is None
        table = {k: v.replace("/moe/", "/mlp/") for k, v in TABLE.items()}
        mistral = context()
        mistral["_program_report"] = {"scopes": table, "module": "jit_prog"}
        assert reader(name).read(mistral) is None
    else:
        assert reader(name).read(bare) is None
        assert reader(name).COUNTERS
