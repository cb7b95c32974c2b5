"""The reader of the share of the held experts' passes whose forward
the backward pass runs again, on windows made by hand."""

import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "moe_recomputed_pass_share"
spec = importlib.util.spec_from_file_location(
    "reader_" + NAME, os.path.join(os.path.dirname(HERE), "layer_metrics",
                                   NAME + ".py"))
reader = importlib.util.module_from_spec(spec)
spec.loader.exec_module(reader)
PASSES, RECOMPUTED = reader.COUNTERS


def window(start, end):
    return {"counters": {"window_start": start, "window_end": end}}


def test_counters_are_the_programs_sums():
    from horovod_tpu.models.transformer import MOE_DEVICE_SUMS

    assert reader.COUNTERS == list(MOE_DEVICE_SUMS[3:])


@pytest.mark.parametrize("passes, recomputed, want", [
    (200.0, 0.0, 0.0),          # 50 steps x 4 routed layers, one pass each
    (208.0, 8.0, 8 / 208),      # eight layer-steps took a second pass
    (600.0, 400.0, 2 / 3)])     # every layer three passes
def test_share_of_the_windows_passes(passes, recomputed, want):
    start = {PASSES: 40.0, RECOMPUTED: 3.0}
    end = {PASSES: 40.0 + passes, RECOMPUTED: 3.0 + recomputed}
    assert reader.read(window(start, end)) == pytest.approx(want)


def test_nothing_to_read_in_a_program_without_the_counters():
    """The parent commit and a model without a routed layer: unknown
    counters read 0 at both ends of the window."""
    zero = dict.fromkeys(reader.COUNTERS, 0.0)
    assert reader.read(window(zero, dict(zero))) is None


def test_listed_for_the_routed_cells():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = (m for m in bench["per_layer"] if m["name"] == NAME)
    routed = [w["name"] for w in bench["workloads"]
              if w["config"] in ("trinity-mini-l5-ep8", "smallthinker-21b-l4-ep4")]
    assert entry["workloads"] == routed and entry["moves"] \
        == "tokens_per_s_per_chip" and entry["source"] == "program_counter"
