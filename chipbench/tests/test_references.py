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


def cnn(mix="b128-1chip"):
    from chipbench.adapters import cnn_train as adapter
    from chipbench.inputs import images
    from chipbench.references import cnn_train as reference

    return (load("configs", "resnet50"), load("workloads", mix),
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


def cnn_program_loss(net, aux):
    """The adapter's loss of the program's ``net`` on a rank's ``(images,
    labels)``, the new batch statistics dropped."""
    def program(p, rows):
        images, labels = rows
        logits, _ = net.apply({"params": p, "batch_stats": aux}, images,
                              train=True, mutable=["batch_stats"])
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), labels[:, None], axis=-1))

    return program


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

    got, got_grads = jax.value_and_grad(cnn_program_loss(net, aux))(
        params, (images, labels))
    want, want_grads = jax.value_and_grad(
        lambda p: reference.batch_loss(
            config, precision.products("float32"), p, images, labels))(params)
    assert float(got) == pytest.approx(float(want), abs=1e-5)
    got_norms = weights.leaf_norms(got_grads)
    for path, norm in weights.leaf_norms(want_grads).items():
        assert float(got_norms[path]) == pytest.approx(float(norm),
                                                       rel=1e-3), path


def cnn_ranks_case(seed=SEEDS[0]):
    """The four-rank mix at the rehearsal's sizes: (config, workload,
    reference, float32 products, parameters, the four ranks' rows)."""
    from chipbench.references import precision

    config, workload, _, reference, make = cnn("b128-dp4")
    key = weights.seed_key(seed)
    params = weights.make(key, reference.param_spec(config))
    batch = make(jax.random.fold_in(key, 1), config, workload,
                 workload["ranks"] * workload["batch"])
    return (config, workload, reference, precision.products("float32"),
            params, batch)


def quarters(batch, ranks):
    return [jax.tree.map(
        lambda a: a.reshape((ranks, -1) + a.shape[1:])[rank], batch)
        for rank in range(ranks)]


def mean_of(trees):
    return jax.tree.map(lambda *leaves: sum(leaves) / len(leaves), *trees)


def test_cnn_mix_of_four_ranks_is_the_one_chip_mix_on_each():
    """``b128-dp4`` is ``b128-1chip`` but for the ranks (and what it
    says of itself): the two cells differ in nothing else."""
    one, four = (load("workloads", mix, part=None) for mix in
                 ("b128-1chip", "b128-dp4"))
    with open(os.path.join(HERE, "..", "workloads", "b128-dp4.json")) as f:
        whole = json.load(f)
    with open(os.path.join(HERE, "..", "workloads", "b128-1chip.json")) as f:
        assert {k for k, v in json.load(f).items() if whole[k] != v} \
            == {"why", "ranks"}
    assert (one["ranks"], four["ranks"]) == (1, 4)
    assert (four["batch"], four["input"]["dtype"]) == (8, "bfloat16")
    assert whole["batch"] == 128 and whole["optimizer"] == {
        "name": "sgd", "learning_rate": 0.1, "momentum": 0.9}
    assert [whole[k] for k in (
        "check_steps", "warmup_steps", "steps_per_reading",
        "steps_in_flight", "trace_steps", "samples_per_row")] \
        == [3, 10, 6, 24, 20, 1]


def test_cnn_reference_of_four_ranks_is_the_mean_of_four_of_one():
    """``ranks`` 4: the loss and the gradient are the means of four
    ``ranks`` 1 calls on the quarters, to float32 rounding: BatchNorm's
    statistics are a rank's own."""
    config, workload, reference, products, params, batch = cnn_ranks_case()
    ranks = workload["ranks"]
    got, got_grads = jax.jit(reference.loss_and_grad(
        config, products, ranks))(params, batch)
    alone = jax.jit(reference.loss_and_grad(config, products, 1))
    each = [alone(params, quarter) for quarter in quarters(batch, ranks)]
    want = sum(float(loss) for loss, _ in each) / ranks
    assert float(got) == pytest.approx(want, abs=1e-6)
    want_grads = mean_of([grads for _, grads in each])
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_grads),
                            jax.tree.leaves(want_grads)):
        assert float(jnp.max(jnp.abs(a - b))) <= \
            2e-5 * float(jnp.max(jnp.abs(b))), \
            jax.tree_util.keystr(path)


def test_cnn_reference_of_four_ranks_matches_the_program_in_float32():
    """The program's own loss function in float32, a rank's rows at a
    time, the means over the ranks as its all-reduce takes them."""
    from horovod_tpu.models import ResNet

    config, workload, reference, products, params, batch = cnn_ranks_case()
    ranks = workload["ranks"]
    aux = weights.make(weights.seed_key(SEEDS[0]),
                       reference.aux_spec(config))
    net = ResNet(stage_sizes=config["stage_sizes"],
                 num_classes=config["num_classes"],
                 num_filters=config["num_filters"], dtype=jnp.float32)

    a_rank = jax.jit(jax.value_and_grad(cnn_program_loss(net, aux)))
    each = [a_rank(params, quarter) for quarter in quarters(batch, ranks)]
    got = sum(float(loss) for loss, _ in each) / ranks
    got_norms = weights.leaf_norms(mean_of([grads for _, grads in each]))
    want, want_grads = jax.jit(reference.loss_and_grad(
        config, products, ranks))(params, batch)
    assert got == pytest.approx(float(want), abs=1e-5)
    for path, norm in weights.leaf_norms(want_grads).items():
        assert float(got_norms[path]) == pytest.approx(float(norm),
                                                       rel=1e-3), path


def test_cnn_statistics_over_all_ranks_rows_are_another_gradient():
    """The planted fault is a fault: BatchNorm's statistics taken over
    all four ranks' rows at once (the reference as it was, handed the
    whole batch) give a gradient the cell's three ways of comparing
    tell from the ranks' own."""
    from chipbench.run import NORM_GAPS

    config, workload, reference, products, params, batch = cnn_ranks_case()
    ranks = workload["ranks"]
    own, own_grads = jax.jit(reference.loss_and_grad(
        config, products, ranks))(params, batch)
    whole, whole_grads = jax.jit(reference.loss_and_grad(
        config, products, 1))(params, batch)
    own_norms, whole_norms = (jax.device_get(weights.leaf_norms(g))
                              for g in (own_grads, whole_grads))
    found = {way: NORM_GAPS[way](whole_norms, own_norms)[0]
             for way in ("mean_kernel", "worst_kernel", "worst_other")}
    print(float(own), float(whole), found)
    assert found["mean_kernel"] > 0.05 and found["worst_kernel"] > 0.2


# recorded from the parent commit (ce1aa26, before the reference took a
# mix's ranks) at the one-chip mix's rehearsal sizes, seed 5: the sha256
# of its step's StableHLO and, on this sandbox's CPU, its numbers
PARENT_ONE_RANK = {
    "stablehlo_sha256":
        "d3c3e021d4cc7eb431ca3974bd0e983dccfd5a81e867525ac332f56ea3028e28",
    "losses": [2.685141086578369, 1.5232871770858765, 1.253929853439331],
    "grad_norms": {"['conv_init']['kernel']": 35.06978988647461,
                   "['head']['bias']": 0.4678323268890381,
                   "['BottleneckBlock_3']['BatchNorm_2']['scale']":
                       0.24187836050987244},
    "delta_norms": {"['conv_init']['kernel']": 10.076173782348633,
                    "['head']['bias']": 0.12170984596014023,
                    "['BottleneckBlock_3']['BatchNorm_2']['scale']":
                        0.0820041298866272}}


def test_cnn_reference_of_one_rank_is_the_parents_program():
    """With ``ranks`` 1 the reference compiles the program it compiled
    before it knew of ranks, byte for byte (so the one-chip cell's
    limits stand unread), and gives the numbers recorded from it (bit
    for bit where the CPU sums in the recording's order; the order
    moves with the cores, by 1e-5 at most)."""
    import hashlib

    config, workload, _, reference, make = cnn()
    key = weights.seed_key(SEEDS[0])
    batch = make(jax.random.fold_in(key, 1), config, workload,
                 workload["batch"])
    params = weights.make(key, reference.param_spec(config))
    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        text = reference.step_program(config, workload).lower(
            params, jax.tree.map(jnp.zeros_like, params), batch).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PARENT_ONE_RANK["stablehlo_sha256"]
    found = reference.follow(config, workload, key, batch, 3)
    assert found["losses"] == pytest.approx(PARENT_ONE_RANK["losses"],
                                            rel=1e-5)
    for name in ("grad_norms", "delta_norms"):
        for leaf, want in PARENT_ONE_RANK[name].items():
            assert float(found[name][leaf]) == pytest.approx(want, rel=1e-5)


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


def test_a_null_limit_is_read_and_not_compared():
    """``limits/resnet50-b128-dp4.json`` reads its first two steps'
    losses and holds them to nothing (no reading they would have to
    stay under): each is printed beside ``null`` and cannot make a run
    incorrect; the third step's still can."""
    from chipbench.run import Compare

    limits = load("limits", "resnet50-b128-dp4", part="limits")
    assert limits["loss_abs_gap"][:2] == [None, None]
    norms = {"['a']['kernel']": 1.0, "['a']['bias']": 1.0}
    ref = {"losses": [7.0, 6.5, 6.0], "grad_norms": norms,
           "delta_norms": norms}
    program = dict(ref, losses=[7.5, 6.0, 6.0])
    compare = Compare()
    for name, value, limit, note in gaps(program, ref, limits):
        compare.check(name, value, limit, note)
    assert compare.ok and compare.numbers["loss_step1_abs_gap"] == [0.5, None]
    assert "compared loss_step1_abs_gap 0.5 limit None " \
        "(program 7.500000 reference 7.000000)" in compare.lines()
    compare.check(*gaps(dict(ref, losses=[7.0, 6.5, 6.5]), ref, limits)[2])
    assert not compare.ok


def test_mean_other_is_the_mean_of_what_worst_other_takes_the_worst_of():
    """``mean_other``: the leaves that are no kernel, each gap against
    the reference's norm of the leaf or of the median leaf, the mean of
    them; a state left unchanged reads a leaf under the median as its
    share of the median, so under 1; ``resnet50-b128-dp4`` holds the
    change by it under that reading and reads the first gradient's."""
    from chipbench.run import NORM_GAPS

    ref = {"['a']['kernel']": 4.0, "['a']['bias']": 2.0,
           "['b']['scale']": 0.5, "['b']['bias']": 1.0,
           "['c']['kernel']": 1.0}         # the median leaf: 1.0
    got = dict(ref, **{"['a']['bias']": 3.0, "['b']['scale']": 0.75,
                       "['c']['kernel']": 9.0})
    value, note = NORM_GAPS["mean_other"](got, ref)
    assert value == pytest.approx((0.5 + 0.25 + 0.0) / 3)
    assert note == "3 other leaves"
    assert NORM_GAPS["worst_other"](got, ref) == (0.5, "['a']['bias']")
    unchanged = dict.fromkeys(ref, 0.0)
    assert NORM_GAPS["mean_other"](unchanged, ref)[0] == pytest.approx(
        (1.0 + 0.5 + 1.0) / 3)
    limits = load("limits", "resnet50-b128-dp4", part="limits")
    assert limits["grad_norm_gap"]["mean_other"] is None
    assert limits["delta_norm_gap"]["mean_other"] < 0.458 / 3


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
@pytest.mark.parametrize("mix, cell, rows", [
    ("b128-1chip", "resnet50-b128-1chip", 32),
    ("b128-dp4", "resnet50-b128-dp4", 64)])
def test_cnn_control_fails_where_the_program_passes(mix, cell, rows, seed):
    """ResNet-50 at full depth and widths on 32 images of 64 x 64, or
    16 for each of four ranks (what a test run can hold; the
    rehearsal's eight images of 32 x 32 leave 1 x 1 feature maps, where
    BatchNorm in bfloat16 swings by tens of percent).  The numbers and
    the control are the cell's: the first gradient's norm gap over the
    kernels (mean and worst) and over the other leaves (worst), the
    program's gradient the mean over the ranks of each rank's on its
    own rows, and the reference with every product in int8, here
    against the limits of ``limits/<cell>.json``'s ``control_test``."""
    from horovod_tpu.models import ResNet

    from chipbench.references import precision
    from chipbench.run import NORM_GAPS

    _, workload, _, reference, make = cnn(mix)
    ranks = workload["ranks"]
    with open(os.path.join(HERE, "..", "configs", "resnet50.json")) as f:
        config = dict(json.load(f), image_size=64)
    control_mode = load("limits", cell, part="control")
    limits = load("limits", cell, part="control_test")["grad_norm_gap"]
    key = weights.seed_key(seed)
    params = weights.make(key, reference.param_spec(config))
    aux = weights.make(key, reference.aux_spec(config))
    batch = make(jax.random.fold_in(key, 1), config, workload, rows)
    net = ResNet(stage_sizes=config["stage_sizes"],
                 num_classes=config["num_classes"],
                 num_filters=config["num_filters"])

    def norms(mode):
        return jax.device_get(weights.leaf_norms(jax.jit(
            reference.loss_and_grad(config, precision.products(mode),
                                    ranks))(params, batch)[1]))

    want = norms("float32")
    a_rank = jax.jit(jax.grad(cnn_program_loss(net, aux)))
    got = jax.device_get(weights.leaf_norms(mean_of(
        [a_rank(params, quarter) for quarter in quarters(batch, ranks)])))
    control = norms(control_mode)
    sound = {way: NORM_GAPS[way](got, want)[0] for way in limits}
    wrong = {way: NORM_GAPS[way](control, want)[0] for way in limits}
    print("sound", sound, "control", wrong)
    assert all(sound[way] <= limits[way] for way in limits), sound
    assert any(wrong[way] > limits[way] for way in limits), wrong
