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
    # first stands in their places
    import numpy as np
    import horovod_tpu as hvd
    if hvd.rank() != hvd.size() - 1 or not hasattr(batch, "shape"):
        return batch    # staged already: place_batch left them out
    rows = np.asarray(batch)
    return np.repeat(rows[:1], len(rows), axis=0)

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
    # a part of the batch never reaches the step (token rows only),
    # where one rank stages its rows or where several hand them over
    "a_ranks_rows_left_out": (
        "return new, loss", "new, loss = step(state, leave_out(batch))",
        "leave_out(batch)"),
}
LM_CELLS = [w["name"] for w in BENCH["workloads"]
            if w["config"] == BENCH["workloads"][0]["config"]]


@pytest.mark.parametrize("cell, fault", [
    (CELLS[0], "step_returns_its_state_unchanged"),
    (CELLS[0], "loss_altered_where_it_is_produced"),
    *((cell, "a_ranks_rows_left_out") for cell in LM_CELLS)])
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
