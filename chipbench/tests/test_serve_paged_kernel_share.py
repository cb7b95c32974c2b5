"""The reader of the share of a served window's decode ticks whose
attention read the paged cache in place, on windows made by hand."""

import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "serve_paged_kernel_share"
spec = importlib.util.spec_from_file_location(
    "reader_" + NAME, os.path.join(os.path.dirname(HERE), "layer_metrics",
                                   NAME + ".py"))
reader = importlib.util.module_from_spec(spec)
spec.loader.exec_module(reader)
TICKS, KERNEL_TICKS = reader.COUNTERS


def window(start, end):
    return {"counters": {"window_start": start, "window_end": end}}


@pytest.fixture
def program_counts(monkeypatch):
    """A process whose programs count their decode ticks."""
    monkeypatch.setattr(reader, "counted", lambda name: True)


def test_counters_are_the_programs_own():
    from horovod_tpu import telemetry

    assert reader.COUNTERS == [telemetry.SERVE_DECODE_TICKS_FAMILY,
                               telemetry.SERVE_PAGED_KERNEL_TICKS_FAMILY]


@pytest.mark.parametrize("ticks, through_kernel, want", [
    (1042.0, 1042.0, 1.0),    # a 20 s window of 19 ms ticks, all in place
    (413.0, 0.0, 0.0)])       # the programs took the XLA form
def test_share_of_the_windows_ticks(program_counts, ticks, through_kernel,
                                    want):
    # warm-up and the check ticked before the window opened
    start = {TICKS: 391.0, KERNEL_TICKS: 391.0 if through_kernel else 0.0}
    end = {TICKS: 391.0 + ticks,
           KERNEL_TICKS: start[KERNEL_TICKS] + through_kernel}
    assert reader.read(window(start, end)) == pytest.approx(want)


def test_nothing_to_read_in_a_window_without_a_decode_tick(program_counts):
    same = {TICKS: 391.0, KERNEL_TICKS: 391.0}
    assert reader.read(window(same, dict(same))) is None


def test_nothing_to_read_in_a_program_without_the_counter(monkeypatch):
    """The parent commit's programs count no ticks: the registry reads
    both unknown names as 0 at both ends, and the reader reports
    nothing, not a share of 0."""
    from horovod_tpu import telemetry
    from horovod_tpu.telemetry.registry import MetricRegistry

    # a registry in which nothing ever ticked
    monkeypatch.setattr(telemetry, "registry", MetricRegistry)
    zero = dict.fromkeys(reader.COUNTERS, 0.0)
    assert reader.read(window(zero, dict(zero))) is None
    # nor would a tick count alone do (a program that counted ticks and
    # had no kernel to count)
    assert reader.read(window(zero, {TICKS: 400.0, KERNEL_TICKS: 0.0})) \
        is None


def test_a_program_that_ticked_is_counted(monkeypatch):
    """``PagedKVPrograms.decode`` makes both counters at its first
    tick, advanced or not: from then on the reader reports, 0.0 where
    the XLA form ran."""
    from horovod_tpu import telemetry
    from horovod_tpu.telemetry.registry import MetricRegistry

    reg = MetricRegistry()
    monkeypatch.setattr(telemetry, "registry", lambda: reg)
    telemetry.count_serve_decode_tick(False)
    start = {TICKS: 1.0, KERNEL_TICKS: 0.0}
    assert reader.read(window(start, {TICKS: 5.0, KERNEL_TICKS: 0.0})) \
        == 0.0
    assert reader.read(window(start, {TICKS: 5.0, KERNEL_TICKS: 4.0})) \
        == 1.0


def test_listed_for_the_saturated_served_cell():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "ratio", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "token_gap_ms_p50",
        "workloads": ["mistral7b-chat-saturated-1chip"]}
    cell = {c["name"]: c for c in bench["workloads"]}[entry["workloads"][0]]
    assert cell["chips"] == 1
