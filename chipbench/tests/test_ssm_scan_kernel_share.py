"""The reader of the share of the scans' chunks that went through the
kernel pair, on windows made by hand."""

import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "ssm_scan_kernel_share"
spec = importlib.util.spec_from_file_location(
    "reader_" + NAME, os.path.join(os.path.dirname(HERE), "layer_metrics",
                                   NAME + ".py"))
reader = importlib.util.module_from_spec(spec)
spec.loader.exec_module(reader)
CHUNKS, KERNEL_CHUNKS = reader.COUNTERS


def window(start, end):
    return {"counters": {"window_start": start, "window_end": end}}


@pytest.fixture
def program_counts(monkeypatch):
    """A process whose program keeps the kernel-chunks sum."""
    monkeypatch.setattr(reader, "counted", lambda name: True)


def test_counters_are_the_programs_sums():
    from horovod_tpu.models.mamba import SSM_DEVICE_SUMS

    assert reader.COUNTERS == list(SSM_DEVICE_SUMS[1:])


@pytest.mark.parametrize("chunks, through_kernels, want", [
    (11520.0, 11520.0, 1.0),     # 40 steps x 9 layers x 32 chunks, all
    (11520.0, 0.0, 0.0),         # every layer fell back to the XLA form
    (11520.0, 10240.0, 8 / 9)])  # one layer of nine fell back
def test_share_of_the_windows_chunks(program_counts, chunks,
                                     through_kernels, want):
    start = {CHUNKS: 288.0, KERNEL_CHUNKS: 288.0}
    end = {CHUNKS: 288.0 + chunks, KERNEL_CHUNKS: 288.0 + through_kernels}
    assert reader.read(window(start, end)) == pytest.approx(want)


def test_nothing_to_read_without_a_scan(program_counts):
    zero = dict.fromkeys(reader.COUNTERS, 0.0)
    assert reader.read(window(zero, dict(zero))) is None


def test_nothing_to_read_in_a_program_without_the_counter():
    """The parent commit's scans count their chunks and no kernel
    chunks: the registry reads the unknown name as 0 at both ends, and
    the reader reports nothing, not a share of 0."""
    from horovod_tpu import telemetry

    assert telemetry.registry().get(KERNEL_CHUNKS) is None
    start = {CHUNKS: 288.0, KERNEL_CHUNKS: 0.0}
    end = {CHUNKS: 11808.0, KERNEL_CHUNKS: 0.0}
    assert reader.read(window(start, end)) is None


def test_a_program_that_declares_the_sum_is_counted(monkeypatch):
    """``ops/device_sums`` makes a counter of every declared name at the
    first read, advanced or not: from then on the reader reports."""
    from horovod_tpu import telemetry
    from horovod_tpu.telemetry.registry import MetricRegistry

    reg = MetricRegistry()
    monkeypatch.setattr(telemetry, "registry", lambda: reg)
    start = {CHUNKS: 0.0, KERNEL_CHUNKS: 0.0}
    end = {CHUNKS: 288.0, KERNEL_CHUNKS: 0.0}
    assert reader.read(window(start, end)) is None
    reg.counter(KERNEL_CHUNKS, "help").labels().inc(0)
    assert reader.read(window(start, end)) == 0.0


def test_listed_for_the_state_space_cell():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "ratio", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "tokens_per_s_per_chip",
        "workloads": ["granite-4.0-h-micro-s8k-1chip"]}
