"""Each plain reference against the program at the rehearsal sizes on
the CPU, and the control (the reference in fp8) against the limits."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import weights
from chipbench.run import gaps, with_rehearsal

HERE = os.path.dirname(os.path.abspath(__file__))
SEEDS = (5, 6, 2**31 + 7)


def load(directory, name, part=None):
    with open(os.path.join(HERE, "..", directory, name + ".json")) as f:
        data = json.load(f)
    return data[part] if part else with_rehearsal(data, True)


def lm(mix="s4k-1chip"):
    from chipbench.adapters import lm_train as adapter
    from chipbench.inputs import tokens
    from chipbench.references import lm_train as reference

    return (load("configs", "mistral7b-l2"), load("workloads", mix),
            adapter, reference, tokens.make)


def cnn():
    from chipbench.adapters import cnn_train as adapter
    from chipbench.inputs import images
    from chipbench.references import cnn_train as reference

    return (load("configs", "resnet50"), load("workloads", "b128-1chip"),
            adapter, reference, images.make)


@pytest.mark.parametrize("case", [lm, cnn])
def test_reference_tree_is_the_programs(case):
    config, workload, adapter, reference, _ = case()
    params, aux = adapter.param_shapes(config, workload)
    assert weights.shapes(reference.param_spec(config)) == \
        weights.shapes(params)
    if aux is not None:
        assert weights.shapes(reference.aux_spec(config)) == \
            weights.shapes(aux)


def test_lm_loss_and_gradient_match_the_program_in_float32():
    """The program's own model in float32 with its dense attention is
    the reference, to rounding: GQA, the window (48 < S = 64), rotary
    positions, the tied head and the shifted targets all agree."""
    from horovod_tpu.models import TransformerLM, lm_loss

    from chipbench.references import precision

    config, workload, adapter, reference, make = lm()
    key = weights.seed_key(SEEDS[0])
    params = weights.make(key, reference.param_spec(config))
    batch = make(key, config, workload, 2)
    import dataclasses
    model = TransformerLM(dataclasses.replace(
        adapter.program_config(config, workload), dtype=jnp.float32,
        remat=False))

    def program(p):
        logits = model.apply({"params": p}, batch)
        return lm_loss(logits[:, :-1], batch[:, 1:])

    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.value_and_grad(program)(params)
    want, want_grads = jax.value_and_grad(
        lambda p: reference.batch_loss(
            config, precision.products("float32")[0], p, batch))(params)
    assert float(got) == pytest.approx(float(want), abs=2e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_grads),
                            jax.tree.leaves(want_grads)):
        assert float(jnp.max(jnp.abs(a - b))) <= \
            1e-4 * float(jnp.max(jnp.abs(b))), jax.tree_util.keystr(path)


def test_cnn_loss_and_gradient_match_the_program_in_float32():
    from horovod_tpu.models import ResNet

    from chipbench.references import precision

    config, workload, _, reference, make = cnn()
    key = weights.seed_key(SEEDS[0])
    params = weights.make(key, reference.param_spec(config))
    aux = weights.make(key, reference.aux_spec(config))
    images, labels = make(key, config, workload, 8)
    net = ResNet(stage_sizes=config["stage_sizes"],
                 num_classes=config["num_classes"],
                 num_filters=config["num_filters"], dtype=jnp.float32)

    def program(p):
        logits, _ = net.apply({"params": p, "batch_stats": aux}, images,
                              train=True, mutable=["batch_stats"])
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), labels[:, None], axis=-1))

    got, got_grads = jax.value_and_grad(program)(params)
    want, want_grads = jax.value_and_grad(
        lambda p: reference.batch_loss(
            config, precision.products("float32"), p, images, labels))(params)
    assert float(got) == pytest.approx(float(want), abs=1e-5)
    got_norms = weights.leaf_norms(got_grads)
    for path, norm in weights.leaf_norms(want_grads).items():
        assert float(got_norms[path]) == pytest.approx(float(norm),
                                                       rel=1e-3), path


def follow_program(config, workload, adapter, reference, key, batch):
    """The program's first steps as run.py drives them, in-process."""
    from chipbench.run import Cell, Shared

    cell = Cell.__new__(Cell)
    cell.config, cell.workload, cell.adapter = config, workload, adapter
    cell.reference, cell.rehearse, cell.ranks = reference, True, 1
    cell.spec = reference.param_spec(config)
    cell.aux_spec = reference.aux_spec(config)
    cell.make_weights = lambda k: (
        weights.make(k, cell.spec),
        None if cell.aux_spec is None else weights.make(k, cell.aux_spec))

    def drive(rank, n_ranks):
        step, state, staged = cell.start(key, batch, 0, True)
        return cell.first_steps(step, state, staged, key, True, Shared(1))[1]

    return adapter.launch(workload, drive)[0]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("mix, cell", [
    ("s4k-1chip", "mistral7b-s4k-1chip"), ("s4k-dp4", "mistral7b-s4k-dp4")])
def test_lm_control_fails_where_the_program_passes(mix, cell, seed):
    """The control (the reference with every product in fp8) has to
    come out as not correct against the limits the rehearsal holds the
    bfloat16 program to; the program has to pass them.  The four-rank
    mix (one step followed, and the loss after it) is driven on one
    rank here; run.py's rehearsal drives its four."""
    config, workload, adapter, reference, make = lm(mix)
    workload = dict(workload, ranks=1)
    key = weights.seed_key(seed)
    batch = make(jax.random.fold_in(key, 1), config, workload,
                 workload["batch"])
    steps = workload["check_steps"]
    limits = load("limits", cell, part="rehearsal")
    ref = reference.follow(config, workload, key, batch, steps)
    control = reference.follow(config, workload, key, batch, steps, "fp8")
    program = follow_program(config, workload, adapter, reference, key, batch)
    assert len(ref["losses"]) == len(program["losses"]) \
        == steps + bool(workload.get("check_loss_after"))

    def verdict(found):
        return all(value <= limit
                   for _, value, limit, _ in gaps(found, ref, limits))

    assert verdict(program)
    assert not verdict(control)


def test_loss_after_sees_the_direction_of_the_update():
    """An update of the right size the wrong way keeps every norm the
    cell compares; only the loss after it tells."""
    config, workload, adapter, reference, make = lm("s4k-dp4")
    workload = dict(workload, ranks=1)
    key = weights.seed_key(SEEDS[0])
    batch = make(jax.random.fold_in(key, 1), config, workload,
                 workload["batch"])
    limits = load("limits", "mistral7b-s4k-dp4", part="rehearsal")
    ref = reference.follow(config, workload, key, batch, 1)
    flipped = reference.follow(
        config, dict(workload, optimizer=dict(
            workload["optimizer"],
            learning_rate=-workload["optimizer"]["learning_rate"])),
        key, batch, 1)
    failed = [name for name, value, limit, _ in gaps(flipped, ref, limits)
              if value > limit]
    assert failed == ["loss_step2_abs_gap"]


@pytest.mark.parametrize("seed", SEEDS)
def test_cnn_control_fails_where_the_program_passes(seed):
    """ResNet-50 at full depth and widths on 32 images of 64 x 64 (what
    a test run can hold; the rehearsal's eight images of 32 x 32 leave
    1 x 1 feature maps, where BatchNorm in bfloat16 swings by tens of
    percent).  The numbers and the control are the cell's: the first
    gradient's norm gap over the kernels (mean and worst) and over the
    other leaves (worst), and the reference with every product in int8,
    here against the limits of ``limits/resnet50-b128-1chip.json``'s
    ``control_test``."""
    from horovod_tpu.models import ResNet

    from chipbench.references import precision
    from chipbench.run import NORM_GAPS

    config, workload, _, reference, make = cnn()
    with open(os.path.join(HERE, "..", "configs", "resnet50.json")) as f:
        config = dict(json.load(f), image_size=64)
    control_mode = load("limits", "resnet50-b128-1chip", part="control")
    limits = load("limits", "resnet50-b128-1chip",
                  part="control_test")["grad_norm_gap"]
    key = weights.seed_key(seed)
    params = weights.make(key, reference.param_spec(config))
    aux = weights.make(key, reference.aux_spec(config))
    images, labels = make(jax.random.fold_in(key, 1), config, workload, 32)
    net = ResNet(stage_sizes=config["stage_sizes"],
                 num_classes=config["num_classes"],
                 num_filters=config["num_filters"])

    def program(p):
        logits, _ = net.apply({"params": p, "batch_stats": aux}, images,
                              train=True, mutable=["batch_stats"])
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), labels[:, None], axis=-1))

    def norms(mode):
        return jax.device_get(weights.leaf_norms(jax.jit(jax.grad(
            lambda p: reference.batch_loss(
                config, precision.products(mode), p, images, labels)))(
                    params)))

    want = norms("float32")
    got = jax.device_get(weights.leaf_norms(
        jax.jit(jax.grad(program))(params)))
    control = norms(control_mode)
    sound = {way: NORM_GAPS[way](got, want)[0] for way in limits}
    wrong = {way: NORM_GAPS[way](control, want)[0] for way in limits}
    print("sound", sound, "control", wrong)
    assert all(sound[way] <= limits[way] for way in limits), sound
    assert any(wrong[way] > limits[way] for way in limits), wrong
