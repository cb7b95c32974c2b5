"""The reader of the share of gradient bytes reduced inside the
backward pass, on windows made by hand."""

import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
spec = importlib.util.spec_from_file_location(
    "reader_grad_reduce_in_backward_pct", os.path.join(
        os.path.dirname(HERE), "layer_metrics",
        "grad_reduce_in_backward_pct.py"))
reader = importlib.util.module_from_spec(spec)
spec.loader.exec_module(reader)
ALL, IN_BACKWARD = reader.COUNTERS


def window(start, end):
    return {"counters": {"window_start": start, "window_end": end}}


def test_counters_are_the_programs_families():
    from horovod_tpu import telemetry

    assert reader.COUNTERS == [
        telemetry.STEP_GRAD_REDUCE_BYTES_FAMILY,
        telemetry.STEP_GRAD_REDUCE_IN_BACKWARD_BYTES_FAMILY]


@pytest.mark.parametrize("steps", [1, 40])
def test_share_of_the_window(steps):
    """Mistral-7B's two layers of 2.269 GB: 76.9%, whatever was counted
    before the window."""
    before = {ALL: 3 * 2269184000.0, IN_BACKWARD: 3 * 1745027072.0}
    after = {ALL: (3 + steps) * 2269184000.0,
             IN_BACKWARD: (3 + steps) * 1745027072.0}
    assert reader.read(window(before, after)) == pytest.approx(
        100 * 1745027072 / 2269184000)
    assert 76.8 < reader.read(window(before, after)) < 77.0


def test_nothing_in_the_backward_is_a_reading_of_zero():
    assert reader.read(window({ALL: 0.0, IN_BACKWARD: 0.0},
                              {ALL: 8e9, IN_BACKWARD: 0.0})) == 0.0


@pytest.mark.parametrize("end", [
    {ALL: 0.0, IN_BACKWARD: 0.0},       # one rank, or an older commit:
    {ALL: 5.0, IN_BACKWARD: 5.0}])      # unknown families read 0
def test_nothing_reduced_is_no_reading(end):
    assert reader.read(window(dict(end), end)) is None
