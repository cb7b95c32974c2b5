"""run.py end to end on the CPU: the rehearsal of every cell of
BENCHMARK.json (a four-chip cell on four virtual devices), the refusal
off the chip, and a run whose timed path is broken underneath."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "chipbench", "run.py")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def run(*args, code=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    command = [sys.executable, RUN, *args] if code is None \
        else [sys.executable, "-c", code, *args]
    return subprocess.run(command, cwd=ROOT, env=env, text=True,
                          capture_output=True, timeout=900)


def last_line(done):
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(cell, trace):
    line = last_line(run("--workload", cell, "--seed", str(2**31 + 11),
                         "--seconds", "1", "--trace", str(trace),
                         "--rehearse", "1"))
    chips = {w["name"]: w["chips"] for w in BENCH["workloads"]}[cell]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    # a rehearsal names its platform and reports no device metric
    assert line["device"]["platform"] == "cpu" and line["rehearsal"]
    assert line["device"]["count"] == chips
    assert line["metrics"] == {}


def test_fails_off_the_chip_and_prints_no_result():
    done = run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
               "--trace", "0")
    assert done.returncode != 0
    assert "not 'tpu'" in done.stderr
    assert '"correct"' not in done.stdout


def test_every_list_names_cells_that_exist_and_every_reader_is_listed():
    """A metric's ``workloads`` names cells of BENCHMARK.json and moves
    an end-to-end metric each of them reports; every reader file under
    ``end_to_end/`` and ``layer_metrics/`` has its entry and every
    entry its file; the four-chip cells are a quarter at most."""
    import glob

    reported = {cell: {m["name"] for m in BENCH["end_to_end"]
                       if cell in m.get("workloads", CELLS)}
                for cell in CELLS}
    for group, directory in (("end_to_end", "end_to_end"),
                             ("per_layer", "layer_metrics")):
        for metric in BENCH[group]:
            listed = metric.get("workloads", CELLS)
            assert listed and set(listed) <= set(CELLS), metric["name"]
            assert len(set(listed)) == len(listed), metric["name"]
            for cell in listed if "moves" in metric else ():
                assert metric["moves"] in reported[cell], \
                    (metric["name"], cell)
        files = {os.path.basename(p)[:-3] for p in glob.glob(
            os.path.join(ROOT, "chipbench", directory, "*.py"))}
        # (end_to_end/ also holds the one reader its rates share)
        assert files - {"samples_per_s_per_chip"} \
            == {m["name"] for m in BENCH[group]}
    for cell in CELLS:
        assert "setup_s" in reported[cell] and len(reported[cell]) >= 2
        assert any(cell in m.get("workloads", CELLS)
                   for m in BENCH["per_layer"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert 1 <= len(four) <= max(1, len(CELLS) // 4)


def test_the_collectives_readers_move_the_rate_their_cell_reports():
    """A four-chip cell's readers of layer "SPMD / collectives" move the
    rate that cell reports (tokens or images a second a chip), not a
    tail: the quantities both four-chip cells read have an entry for
    each rate, ``<name>`` and ``<name>.images``, read by one reader."""
    from chipbench.run import load_reader

    rates = {m["name"]: m.get("workloads", CELLS)
             for m in BENCH["end_to_end"]
             if m["name"].endswith("_per_s_per_chip")}
    layer = [m for m in BENCH["per_layer"]
             if m["layer"] == "SPMD / collectives"]
    assert all(m["moves"] in rates for m in layer)
    for cell in (w["name"] for w in BENCH["workloads"] if w["chips"] == 4):
        mine = [m for m in layer if cell in m["workloads"]]
        assert len(mine) >= 4, cell
        assert all(cell in rates[m["moves"]] for m in mine), cell
    for m in layer:
        if m["name"].endswith(".images"):
            assert load_reader("layer_metrics", m["name"]).read.__module__ \
                == "chipbench.layer_metrics." + m["name"][:-len(".images")]


def test_unknown_cell_is_refused():
    done = run("--workload", "no-such-cell", "--seed", "1", "--seconds", "1",
               "--trace", "0", "--rehearse", "1")
    assert done.returncode != 0 and '"correct"' not in done.stdout


BROKEN = """
import sys
sys.path.insert(0, {root!r})
from chipbench import run as harness
from chipbench.adapters import {adapter} as adapter

make_step = adapter.make_step

def leave_out(batch):
    # the last rank's rows but its first are left out of the step: the
    # first stands in their places (token rows, or images and labels)
    import jax
    import numpy as np
    import horovod_tpu as hvd
    if hvd.rank() != hvd.size() - 1:
        return batch
    return jax.tree.map(
        lambda rows: np.repeat(np.asarray(rows)[:1], len(rows), axis=0)
        if hasattr(rows, "shape") else rows,    # else staged already:
        batch)                                  # place_batch left them out

def broken(config, workload, rehearse):
    step = make_step(config, workload, rehearse)

    class Step:
        init_state = step.init_state

        def place_batch(self, batch):
            return step.place_batch({staged})

        def __call__(self, state, batch):
            new, loss = step(state, batch)
            {fault}
    return Step()

adapter.make_step = broken
sys.exit(harness.main(sys.argv[1:]))
"""

FAULTS = {
    # the optimizer's update is thrown away: the state comes back as it
    # went in (the step donates its state, so a copy is handed in)
    "step_returns_its_state_unchanged": (
        "return state, loss",
        "new, loss = step(__import__('jax').tree.map("
        "lambda a: a.copy(), state), batch)", None),
    # the loss is produced, then altered
    "loss_altered_where_it_is_produced": ("return new, loss * 1.01", None,
                                          None),
    # a part of the batch never reaches the step, where one rank stages
    # its rows or where several hand them over
    "a_ranks_rows_left_out": (
        "return new, loss", "new, loss = step(state, leave_out(batch))",
        "leave_out(batch)"),
}
LM_CELLS = [w["name"] for w in BENCH["workloads"]
            if w["config"] == BENCH["workloads"][0]["config"]]
CNN_DP4 = "resnet50-b128-dp4"


@pytest.mark.parametrize("cell, fault", [
    (CELLS[0], "step_returns_its_state_unchanged"),
    (CELLS[0], "loss_altered_where_it_is_produced"),
    *((cell, "a_ranks_rows_left_out") for cell in LM_CELLS),
    (CNN_DP4, "step_returns_its_state_unchanged"),
    (CNN_DP4, "a_ranks_rows_left_out")])
def test_broken_timed_path_is_not_correct(cell, fault):
    """Skips the look for a chip (the rehearsal) and drives the rest of
    a run with the program's step broken underneath: ``correct`` has to
    come out false."""
    config = {c["name"]: c for c in BENCH["configs"]}[
        {w["name"]: w for w in BENCH["workloads"]}[cell]["config"]]
    with open(os.path.join(ROOT, config["file"])) as f:
        adapter = json.load(f)["adapter"]
    returned, called, staged = FAULTS[fault]
    code = BROKEN.format(root=ROOT, adapter=adapter, fault=returned,
                         staged=staged or "batch")
    if called:
        code = code.replace("new, loss = step(state, batch)", called)
    line = last_line(run("--workload", cell, "--seed", "77", "--seconds",
                         "1", "--trace", "0", "--rehearse", "1", code=code))
    assert line["correct"] is False
