"""What the ``looped_lm_train`` configuration (Ouro-2.6B) brings: its FLOP
and parameter count against hand-worked ones, its plain reference
against the program at the rehearsal sizes, the fp8 control failing the
rehearsal's limits where the bfloat16 program passes them, and each new
reader on a table, intervals and counters made by hand.  (The rehearsal
of the new cell is ``test_run.py``'s, which runs every cell of
BENCHMARK.json.)"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import flops, looped_flops, weights
from chipbench import trace_reduce as tr
from chipbench.run import gaps, with_rehearsal
from chipbench.trace_reduce import Op

HERE = os.path.dirname(os.path.abspath(__file__))
CELL, CONFIG, TRAFFIC = "ouro-2.6b-s4k-1chip", "ouro-2.6b-l8", "s4k-b1-1chip"


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
# operations and parameters

def test_flops_per_token_against_a_hand_worked_count():
    """Ouro's eight layers as the cell runs them: four passes, four
    exits, 4,096 tokens a row."""
    config = load("configs", CONFIG, False)
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632           # q k v o, SwiGLU
    assert looped_flops.layer_matmul_params(config) == layer == 51380224
    one_exit = 49152 * 2048 + 2048                      # head, gate
    assert looped_flops.exit_matmul_params(config) == one_exit
    uses = 4 * (8 * layer + one_exit)
    assert looped_flops.matmul_uses_per_token(config) == uses == 2046828544
    # a causal query of a 4,096 row sees 2,048.5 keys on average; 32
    # layer applications, 16 heads of 128, 2 products forward, 4 back
    attended = 32 * 6 * 2 * 16 * 128 * 2048.5
    assert looped_flops.attention_train_flops_per_token(config, 4096) \
        == attended == 1611005952
    total = looped_flops.train_flops_per_token(config, 4096)
    assert total == 6 * uses + attended == 13891977216     # 13.89 GFLOP
    assert total * 4096 == pytest.approx(56.9e12, rel=1e-3)     # a step
    # the four exits' heads weigh 17%, against 3% in the whole model
    head = 6 * 4 * one_exit / total
    assert head == pytest.approx(0.174, abs=0.001)
    whole = dict(config, num_hidden_layers=48)
    assert 6 * 4 * one_exit / looped_flops.train_flops_per_token(
        whole, 4096) == pytest.approx(0.034, abs=0.001)
    # one pass is the plain count of the same layers (``flops.py``)
    once = dict(config, total_ut_steps=1)
    assert looped_flops.train_flops_per_token(once, 4096) \
        == flops.lm_train_flops_per_token(once, 4096) + 6 * 2048


def test_parameter_count_is_the_configurations():
    from chipbench.references import looped_lm_train as reference

    config = load("configs", CONFIG, False)
    n = sum(int(jnp.prod(jnp.asarray(shape))) for shape in
            weights.shapes(reference.param_spec(config)).values())
    # 8 x (4 x 2048^2 + 3 x 2048 x 5632 + 4 x 2048) + 2 x 49152 x 2048
    # + 2048 + 2049
    assert n == 8 * 51388416 + 2 * 100663296 + 2048 + 2049 == 612438017
    assert config["parameters"] == n
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert set(config["published"]) == set(config["reduced"])
    assert config["total_ut_steps"] == 4 and config["remat_policy"] == "full"
    assert {"assumed", "departures", "stands_for"} <= set(config)


def test_published_keys_are_the_catalogs():
    """Every key of the published ``config.json`` as the catalog beside
    the ``model-configs`` guide has it, but the two that are reduced."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    config = load("configs", CONFIG, False)
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["layer_types"] == row["config"]["layer_types"][:8]


# ---------------------------------------------------------------------------
# the reference against the program

def case():
    from chipbench.adapters import looped_lm_train as adapter
    from chipbench.inputs import tokens
    from chipbench.references import looped_lm_train as reference

    return (load("configs", CONFIG, True), load("workloads", TRAFFIC, True),
            adapter, reference, tokens.make)


@pytest.mark.parametrize("rehearse", [True, False])
def test_reference_tree_is_the_programs(rehearse):
    from chipbench.adapters import looped_lm_train as adapter
    from chipbench.references import looped_lm_train as reference

    config = load("configs", CONFIG, rehearse)
    params, aux = adapter.param_shapes(
        config, load("workloads", TRAFFIC, rehearse))
    assert weights.shapes(reference.param_spec(config)) \
        == weights.shapes(params)
    assert aux is None and reference.aux_spec(config) is None


def test_loss_and_gradient_match_the_program_in_float32():
    """The program's looped model in float32 with its dense attention is
    the reference, to rounding: the passes over the same weights, the
    final norm a pass, the gate, the exits' mixing, the entropy."""
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


def test_fp8_control_fails_where_the_bfloat16_program_passes():
    """The rehearsal's limits hold the program as the adapter builds it
    (bfloat16 products, the flash kernel interpreted) and catch the
    reference computed in fp8."""
    import optax

    config, workload, adapter, reference, make = case()
    with open(os.path.join(HERE, "..", "limits", CELL + ".json")) as f:
        limits = json.load(f)["rehearsal"]
    loss_fn = adapter.loss_fn(config, workload, True)
    optimizer = adapter.optimizer(workload)
    spec = reference.param_spec(config)
    for seed in (5, 2**31 + 7):
        key = weights.seed_key(seed)
        batch = make(jax.random.fold_in(key, 1), config, workload, 1)
        sound = reference.follow(config, workload, key, batch, 2)
        control = reference.follow(config, workload, key, batch, 2, "fp8")
        over = [name for name, value, limit, _ in
                gaps(control, sound, limits) if value > limit]
        assert over, seed

        params = first = weights.make(key, spec)
        state, found = optimizer.init(params), {"losses": []}
        for i in range(2):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            found["losses"].append(float(loss))
            if i == 0:
                found["grad_norms"] = weights.leaf_norms(grads)
            updates, state = optimizer.update(grads, state, params)
            params = optax.apply_updates(params, updates)
        found["delta_norms"] = weights.leaf_norms(
            jax.tree.map(lambda a, b: a - b, params, first))
        assert not [name for name, value, limit, _ in
                    gaps(found, sound, limits) if value > limit], seed


# ---------------------------------------------------------------------------
# the readers, on a table and intervals made by hand

STEP = "jit(prog)/hvd_step/loss_and_grad/"
FWD = STEP + "vmap(jvp(TransformerLM))/TransformerLM._loop/loop/"
BWD = STEP + "vmap(transpose(jvp(TransformerLM)))/TransformerLM._loop/loop/"
LAYER = "while/body/closed_call/periods/layer_0/"
REMAT = "while/body/closed_call/periods/periods/checkpoint/" \
    "rematted_computation/layer_0/"
BACK = "while/body/closed_call/periods/periods/checkpoint/layer_0/"
TABLE = {
    # the layers' own work, in the loop
    "fusion.1": FWD + LAYER + "full_attention/attn/wq/dot_general",
    "attn.2": FWD + LAYER + "full_attention/attn/flash_fwd/flash_fwd/"
    "pallas_call",
    "fusion.3": FWD + LAYER + "mlp/wi_up/dot_general",
    "fusion.4": FWD + LAYER + "ln_post_mlp/mul",
    "fusion.5": FWD + "ln_final/mul",
    "fusion.6": BWD + REMAT + "full_attention/attn/wo/dot_general",
    "fusion.7": BWD + BACK + "mlp/wo/transpose(jvp(dot_general))",
    # the loop's glue: slices, residual adds, the gradient's sum over
    # the passes
    "fusion.8": FWD + "while/body/dynamic_slice",
    "fusion.9": FWD + LAYER + "add",
    "fusion.10": BWD + "add_any",
    "fusion.11": BWD + "while/body/dynamic_update_slice",
    # outside the loop
    "fusion.12": STEP + "vmap(jvp(TransformerLM))/exit_gate/"
    "early_exit_gate/dot_general",
    "fusion.13": STEP + "vmap(jvp(exit_gate))/exp",
    "fusion.14": STEP + "vmap(jvp(lm_head_ce))/while/body/dot_general",
    "fusion.15": "jit(prog)/hvd_step/optimizer/mul",
    # no step scope
    "fusion.16": "jit(prog)/vmap(jvp(TransformerLM))/loop/mul",
}


def traced(trace_steps=2):
    """Every instruction of ``TABLE`` once a step for 1 ms on one chip,
    and one the table does not hold."""
    rows = []
    for step in range(trace_steps):
        for i, name in enumerate(list(TABLE) + ["unknown.99"]):
            mark = " = custom-call bf16[8]" + tr.KERNEL_MARK \
                if name.startswith("attn.") else " = fusion f32[4]"
            start = (step * 20 + i) * 1e-3
            rows.append(Op(0, tr.OPS_LINE, name + mark, start, start + 1e-3))
    return rows


def context(**more):
    ctx = {"trace": traced(), "trace_steps": 2, "ranks": 1,
           "config": load("configs", CONFIG, False),
           "workload": load("workloads", TRAFFIC, False),
           "peaks": flops.peaks("TPU v5 lite"),
           "window": {"steps": 30, "samples_per_step": 4096},
           "_program_report": {"scopes": TABLE, "module": "jit_prog"}}
    ctx.update(more)
    return ctx


EXIT_PASS = reader("loop_expected_exit_pass")


def window(ctx, tokens=0.0, masses=()):
    """The counters around a window in which ``tokens`` were scored and
    pass ``t`` took ``masses[t - 1]`` of them."""
    start = {name: 3.0 for name in EXIT_PASS.COUNTERS}
    end = dict(start)
    end[EXIT_PASS.TOKENS] += tokens
    for name, mass in zip(EXIT_PASS.PASSES, masses):
        end[name] += mass
    ctx["counters"] = {"window_start": start, "window_end": end}
    return ctx


def test_time_under_the_loop():
    ctx = context()
    # eleven instructions lie under ``loop`` with a step scope
    assert reader("loop_ms_per_step").read(ctx) == pytest.approx(11.0)
    # four of them under none of attn, mlp and the norms
    assert reader("loop_glue_ms_per_step").read(ctx) == pytest.approx(4.0)


def test_expected_exit_pass_from_the_programs_sums():
    """30 steps of 4,095 scored tokens; half leave at the first pass, a
    quarter at the second, an eighth at each of the last two."""
    tokens = 30 * 4095.0
    ctx = window(context(), tokens,
                 [tokens / 2, tokens / 4, tokens / 8, tokens / 8])
    assert EXIT_PASS.read(ctx) == pytest.approx(
        0.5 + 2 * 0.25 + 3 * 0.125 + 4 * 0.125)
    everything_last = window(context(), tokens, [0, 0, 0, tokens])
    assert EXIT_PASS.read(everything_last) == pytest.approx(4.0)
    assert EXIT_PASS.COUNTERS[0] == "horovod_loop_tokens_total"
    # the program's own names
    from horovod_tpu.models import transformer

    assert list(transformer.loop_device_sums(8)) == EXIT_PASS.COUNTERS


@pytest.mark.parametrize("name", [
    "loop_ms_per_step", "loop_glue_ms_per_step", "loop_expected_exit_pass"])
def test_readers_find_nothing_in_a_program_without_the_names(name):
    """The parent commit: no report, unknown counters read 0; no trace;
    and a Mistral step: a report, no ``loop`` scope."""
    bare = window(context(_program_report=None))
    assert reader(name).read(bare) is None
    no_trace = window(context(trace=None))
    assert reader(name).read(no_trace) is None
    table = {k: v.replace("TransformerLM._loop/loop/", "layers/")
             for k, v in TABLE.items() if k != "fusion.16"}
    mistral = window(context(
        _program_report={"scopes": table, "module": "jit_prog"}))
    assert reader(name).read(mistral) is None
