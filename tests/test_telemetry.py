"""Telemetry subsystem tests: registry semantics, Prometheus
exposition, snapshot aggregation, the per-worker HTTP endpoint, the
coordinator's job-wide /metrics, and the engine's family catalogue."""

import json
import os
import re
import urllib.request

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import telemetry
from horovod_tpu.telemetry.registry import MetricRegistry

# ONE text-format v0.0.4 validator for tests and the ci.sh metrics
# smoke (conftest puts the repo root on sys.path)
from tools.metrics_smoke import parse_prometheus


# -- registry ----------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = MetricRegistry()
    c = reg.counter("t_total", "help", labelnames=("op",))
    c.labels(op="a").inc()
    c.labels(op="a").inc(2)
    c.labels(op="b").inc(5)
    assert c.total() == 8
    assert c.value(op="a") == 3
    assert c.as_dict() == {"a": 3, "b": 5}
    with pytest.raises(ValueError):
        c.labels(op="a").inc(-1)

    g = reg.gauge("t_gauge", "help")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.total() == 3

    h = reg.histogram("t_seconds", "help", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    snap = reg.snapshot()["t_seconds"]["samples"][0]
    assert snap["counts"] == [1, 1, 1]      # per-bucket + overflow
    assert snap["count"] == 3
    assert snap["sum"] == pytest.approx(5.55)

    # idempotent re-declaration returns the same family; type clashes
    # are errors
    assert reg.counter("t_total", labelnames=("op",)) is c
    with pytest.raises(ValueError):
        reg.gauge("t_total")


def test_registry_label_validation():
    reg = MetricRegistry()
    c = reg.counter("x_total", labelnames=("op",))
    with pytest.raises(ValueError):
        c.labels(wrong="a")
    with pytest.raises(ValueError):
        reg.counter("bad name")


# -- exposition ---------------------------------------------------------------

def test_render_prometheus_valid_and_escaped():
    reg = MetricRegistry()
    reg.counter("esc_total", 'has "quotes"\nand newline',
                labelnames=("k",)).labels(k='v"\\x\n').inc()
    reg.histogram("lat_seconds", "lat", buckets=(0.1, 1.0)).observe(0.5)
    text = telemetry.render_prometheus(reg.snapshot())
    fams = parse_prometheus(text)
    assert fams["esc_total"] == 1
    # histogram: 2 finite buckets + +Inf + sum + count
    assert fams["lat_seconds"] == 5
    assert 'le="+Inf"' in text
    # cumulative bucket semantics
    assert 'lat_seconds_bucket{le="0.1"} 0' in text
    assert 'lat_seconds_bucket{le="1"} 1' in text


def test_merge_snapshots_aggregation():
    a, b = MetricRegistry(), MetricRegistry()
    for reg, val in ((a, 3), (b, 7)):
        reg.counter("c_total", labelnames=("op",)) \
            .labels(op="x").inc(val)
        reg.gauge("g_depth").set(val)
        reg.histogram("h_seconds", buckets=(1.0,)).observe(val / 10)
    merged = telemetry.merge_snapshots([a.snapshot(), b.snapshot()])
    # counters sum
    assert merged["c_total"]["samples"][0]["value"] == 10
    # gauges expose per-worker extremes under an agg label
    gvals = {s["labels"]["agg"]: s["value"]
             for s in merged["g_depth"]["samples"]}
    assert gvals == {"max": 7, "min": 3}
    # histograms merge bucket-wise
    hs = merged["h_seconds"]["samples"][0]
    assert hs["count"] == 2 and hs["counts"] == [2, 0]
    assert hs["sum"] == pytest.approx(1.0)
    # merged output renders
    parse_prometheus(telemetry.render_prometheus(merged))


def test_metrics_server_scrape():
    reg = MetricRegistry()
    reg.counter("probe_total").inc(42)
    server = telemetry.MetricsServer(port=0, registry_fn=lambda: reg)
    port = server.start()
    try:
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10) \
            .read().decode()
        assert "probe_total 42" in text
        parse_prometheus(text)
        payload = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=10)
            .read().decode())
        assert payload["families"]["probe_total"]["samples"][0][
            "value"] == 42
    finally:
        server.stop()


# -- engine integration -------------------------------------------------------

REQUIRED_FAMILIES = (
    "horovod_wire_logical_bytes_total",
    "horovod_wire_actual_bytes_total",
    "horovod_wire_cross_bytes_total",
    "horovod_allreduce_runs_total",
    "horovod_quantized_buckets_total",
    "horovod_fused_allgather_runs_total",
    "horovod_negotiation_seconds",
    "horovod_execution_seconds",
    "horovod_cycle_seconds",
    "horovod_pending_entries",
    "horovod_awaiting_entries",
    "horovod_stalled_tensors",
    "horovod_stall_warnings_total",
    "horovod_program_cache_hits_total",
    "horovod_program_cache_misses_total",
    "horovod_compile_seconds_total",
    "horovod_autotune_samples_total",
    "horovod_autotune_best_score_bytes_per_sec",
    "horovod_elastic_resize_events_total",
    "horovod_world_size",
)


def test_engine_families_and_shims(hvd_shutdown):
    def fn():
        hvd.allreduce(np.ones(256, np.float32), name="m1")
        hvd.allreduce(np.ones(1024, np.float32), name="m2",
                      wire_dtype="int8")
        hvd.allgather(np.ones((2, 2), np.float32), name="mg")
        return True

    assert all(hvd.run(fn, np=2, keep_alive=True))
    snap = hvd.metrics()
    for fam in REQUIRED_FAMILIES:
        assert fam in snap, f"missing family {fam}"
    # deprecated attribute shims read the SAME families — migrating
    # benchmarks must see identical numbers (acceptance criterion)
    from horovod_tpu.common import basics
    eng = basics.engine()
    assert eng.logical_wire_bytes == int(telemetry.counter_total(
        "horovod_wire_logical_bytes_total"))
    assert eng.actual_wire_bytes == int(telemetry.counter_total(
        "horovod_wire_actual_bytes_total"))
    assert eng.quantized_bucket_runs == int(telemetry.counter_total(
        "horovod_quantized_buckets_total")) > 0
    assert eng.algo_runs.get("flat", 0) == int(
        telemetry.counter_total("horovod_allreduce_runs_total",
                                algorithm="flat")) > 0
    # latency histograms saw the ops
    neg = snap["horovod_negotiation_seconds"]["samples"]
    assert sum(s["count"] for s in neg) >= 3
    ops = {s["labels"]["op"] for s in neg}
    assert "ALLREDUCE" in ops and "ALLGATHER" in ops
    exe = snap["horovod_execution_seconds"]["samples"]
    assert sum(s["count"] for s in exe) >= 3
    assert snap["horovod_world_size"]["samples"][0]["value"] == 2
    # the whole catalogue renders as valid exposition text
    parse_prometheus(telemetry.render_prometheus(snap))


def test_compiled_path_cache_metrics(hvd_shutdown):
    hvd.init(num_ranks=1)
    h0 = telemetry.counter_total("horovod_program_cache_hits_total")
    m0 = telemetry.counter_total("horovod_program_cache_misses_total")
    red = hvd.CompiledGroupedAllreduce(op=hvd.Sum, name="tm",
                                       force_program=True)
    x = [np.ones(64, np.float32)]
    red(x)
    assert telemetry.counter_total(
        "horovod_program_cache_misses_total") == m0 + 1
    red(x)
    red(x)
    assert telemetry.counter_total(
        "horovod_program_cache_hits_total") >= h0 + 2
    assert telemetry.counter_total("horovod_compile_seconds_total") > 0


def test_autotune_exports_best_config(hvd_shutdown, monkeypatch):
    monkeypatch.setenv("HOROVOD_AUTOTUNE", "1")
    monkeypatch.setenv("HOROVOD_AUTOTUNE_WARMUP_SAMPLES", "1")
    monkeypatch.setenv("HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE", "2")

    def fn():
        for i in range(10):
            hvd.allreduce(np.ones(512, np.float32), name=f"at.{i % 2}")
        return True

    assert all(hvd.run(fn, np=2))
    snap = hvd.metrics()
    assert telemetry.counter_total(
        "horovod_autotune_samples_total") >= 2
    best = snap["horovod_autotune_best_config"]["samples"]
    assert len(best) == 1       # info-gauge: exactly one current best
    assert set(best[0]["labels"]) == {
        "fusion_threshold_bytes", "cycle_time_ms", "wire", "algorithm",
        "pipeline", "shard_layout", "overlap_bucket", "experts"}
    assert snap["horovod_autotune_best_score_bytes_per_sec"][
        "samples"][0]["value"] > 0


# -- job-wide aggregation over the coordinator --------------------------------

def test_coordinator_job_wide_metrics_endpoint():
    """Workers push snapshots over the KV fabric; the launcher's
    rendezvous service serves the merged job view on /metrics —
    unauthenticated (Prometheus scrapers cannot HMAC-sign)."""
    from horovod_tpu.runner.http.http_server import RendezvousServer
    from horovod_tpu.runner.http.http_client import StoreClient

    server = RendezvousServer(secret=b"s", world_size=2)
    port = server.start()
    try:
        for proc, val in ((0, 10), (1, 32)):
            reg = MetricRegistry()
            reg.counter("horovod_wire_actual_bytes_total",
                        labelnames=("wire",)) \
                .labels(wire="f32").inc(val)
            reg.gauge("horovod_pending_entries",
                      labelnames=("process_set",)) \
                .labels(process_set=0).set(proc + 1)
            client = StoreClient("127.0.0.1", port, b"s")
            client.put(f"/telemetry/{proc}",
                       telemetry.render_json(reg.snapshot(),
                                             proc=proc).encode())
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10) \
            .read().decode()
        parse_prometheus(text)
        assert 'horovod_wire_actual_bytes_total{wire="f32"} 42' in text
        assert ('horovod_pending_entries'
                '{agg="max",process_set="0"} 2') in text
        assert ('horovod_pending_entries'
                '{agg="min",process_set="0"} 1') in text
    finally:
        server.stop()


@pytest.mark.integration
def test_two_process_job_wide_metrics(tmp_path):
    """End-to-end acceptance: a 2-process job serves per-worker AND
    job-wide /metrics in valid Prometheus text covering the required
    families (the ci.sh `metrics` smoke runs the same scenario)."""
    import subprocess
    import sys
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools",
                                      "metrics_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": repo})
    assert proc.returncode == 0, (proc.stdout[-2000:],
                                  proc.stderr[-2000:])
    assert "METRICS SMOKE OK" in proc.stdout


# -- the compiled step's own account (telemetry/programs.py) -----------------

def test_exclusive_seconds_books_each_instant_once():
    from horovod_tpu.telemetry.programs import exclusive_seconds

    trace, lower, backend = 0, 1, 2
    spans = [
        (trace, 0.0, 10.0),         # the outer trace
        (trace, 1.0, 2.0),          # a jitted function traced inside it
        (lower, 3.0, 4.0),          # a concrete value computed while
        (backend, 4.0, 6.0),        # tracing: its own lower + compile
        (lower, 10.0, 13.0),
        (backend, 13.0, 20.0),
    ]
    assert exclusive_seconds(spans) == [7.0, 4.0, 9.0]
    assert exclusive_seconds([]) == [0.0, 0.0, 0.0]


def test_annotate_adds_elapsed_seconds_to_a_counter():
    import time

    from horovod_tpu.utils import profiler

    reg = MetricRegistry()
    child = reg.counter("t_seconds_total", "t").labels()
    entered = []

    class Beside:
        def __enter__(self):
            entered.append("in")

        def __exit__(self, *exc):
            entered.append("out")

    with profiler.annotate("hvd: test", child, Beside()):
        time.sleep(0.01)
    assert 0.01 <= child.value < 1.0
    assert entered == ["in", "out"]
    with profiler.annotate("hvd: test"):        # a name alone still works
        pass


def test_program_reports_survive_shutdown(hvd_shutdown):
    """The benchmark asks after ``hvd.shutdown()``: the reports of the
    programs that ran under the last registry are still answerable,
    and a fresh registry starts an empty list."""
    import optax

    hvd.init(num_ranks=1)
    assert telemetry.program_reports() == []
    step = hvd.make_compiled_train_step(
        lambda p, b: ((b @ p["w"]) ** 2).mean(), optax.sgd(0.1))
    state = step.init_state({"w": np.ones((3, 1), np.float32)})
    state, _ = step(state, np.ones((2, 3), np.float32))
    hvd.shutdown()
    del step, state
    (report,) = telemetry.program_reports()
    assert report["module"] == "jit_prog"
    assert any("hvd_step/optimizer" in path
               for path in report["scopes"].values())
    hvd.init(num_ranks=1)
    assert telemetry.program_reports() == []


# -- the report's tables (telemetry/programs.program_tables) ------------------

_STEP = "jit(prog)/hvd_step/loss_and_grad/"
_MOE = "periods/layer_3/moe/while/body/closed_call/"
_OUTER = {
    "forward": _STEP + "vmap(jvp(TransformerLM))/while/body/closed_call/",
    "transposed": _STEP + "vmap(transpose(jvp(TransformerLM)))/while/body/"
    "closed_call/periods/checkpoint/",
}
_USER = {"forward": "experts/mul", "transposed": "jvp(experts)/mul"}


def _kernel_text(user_path):
    """A grouped-matmul kernel as the TPU compiler leaves it: named
    after itself, its first operands the elements of a metadata call
    that is named after itself too, one data operand a bare copy, and
    its user a fusion behind an asynchronous copy."""
    return f"""HloModule jit_prog, is_scheduled=true

%fused_computation (p: bf16[64,8]) -> bf16[64,8] {{
  %p = bf16[64,8]{{1,0}} parameter(0)
  ROOT %mul.0 = bf16[64,8]{{1,0}} multiply(%p, %p), metadata={{op_name="{user_path}"}}
}}

ENTRY %main.9 (x: bf16[64,16], sizes: s32[4], w: bf16[4,16,8]) -> bf16[64,8] {{
  %x = bf16[64,16]{{1,0}} parameter(0), metadata={{op_name="x"}}
  %sizes = s32[4]{{0}} parameter(1), metadata={{op_name="sizes"}}
  %w = bf16[4,16,8]{{2,1,0}} parameter(2), metadata={{op_name="state['params']['w']"}}
  %copy.1 = bf16[4,16,8]{{2,1,0:T(8,128)(2,1)}} copy(%w)
  %ragged-dot-metadata.1 = (s32[5]{{0}}, s32[9]{{0}}, s32[1]{{0}}) custom-call(%sizes), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-metadata"}}
  %get-tuple-element.1 = s32[5]{{0}} get-tuple-element(%ragged-dot-metadata.1), index=0
  %get-tuple-element.2 = s32[9]{{0}} get-tuple-element(%ragged-dot-metadata.1), index=1
  %ragged-dot-none.3 = bf16[64,8]{{1,0:T(8,128)(2,1)}} custom-call(%get-tuple-element.1, %get-tuple-element.2, %x, %copy.1), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %copy-start.4 = (bf16[64,8]{{1,0:S(1)}}, bf16[64,8]{{1,0}}, u32[]{{:S(2)}}) copy-start(%ragged-dot-none.3)
  %copy-done.4 = bf16[64,8]{{1,0:S(1)}} copy-done(%copy-start.4)
  ROOT %fusion.5 = bf16[64,8]{{1,0}} fusion(%copy-done.4), kind=kLoop, calls=%fused_computation
}}
"""


@pytest.mark.parametrize("variant", ["forward", "transposed"])
def test_compiler_named_kernel_is_booked_by_dataflow(variant):
    """The kernel and its metadata call get the scopes that enclose
    their first scoped user, then their own name: never that user's
    innermost scope (``experts``), never an operand's."""
    from horovod_tpu.telemetry.programs import (instruction_scopes,
                                                program_tables)

    enclosing = _OUTER[variant] + _MOE
    text = _kernel_text(enclosing + _USER[variant])
    tables = program_tables(text)
    scopes = tables["scopes"]
    assert scopes == instruction_scopes(text)
    assert scopes["ragged-dot-none.3"] \
        == enclosing.rstrip("/") + "/ragged-dot-none"
    assert scopes["ragged-dot-metadata.1"] \
        == enclosing.rstrip("/") + "/ragged-dot-metadata"
    # a recovered path is marked as one, a stated path is not
    assert tables["renamed"] == {
        "ragged-dot-none.3": "ragged-dot-none",
        "ragged-dot-metadata.1": "ragged-dot-metadata"}
    # what only moves a recovered result follows it, unmarked
    assert scopes["copy-start.4"] == scopes["copy-done.4"] \
        == scopes["ragged-dot-none.3"]
    assert scopes["get-tuple-element.1"] == scopes["ragged-dot-metadata.1"]
    assert scopes["fusion.5"] == enclosing + _USER[variant]
    # names of the program's own without a scope stay as they are
    assert scopes["x"] == "x" and scopes["w"] == "state['params']['w']"
    assert scopes["copy.1"] == "state['params']['w']"
    assert ("transpose(" in scopes["ragged-dot-none.3"]) \
        == (variant == "transposed")
    assert tables["collectives"] == []


def test_compiler_named_kernel_without_a_scoped_user_keeps_its_name():
    from horovod_tpu.telemetry.programs import program_tables

    text = """HloModule jit_prog, is_scheduled=true

ENTRY %main.4 (x: bf16[64,16], w: bf16[4,16,8]) -> (bf16[64,8]) {
  %x = bf16[64,16]{1,0} parameter(0), metadata={op_name="jit(prog)/hvd_step/loss_and_grad/moe/dispatch/gather"}
  %w = bf16[4,16,8]{2,1,0} parameter(1)
  %ragged-dot-none.1 = bf16[64,8]{1,0} custom-call(%x, %w), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %convert.2 = f32[64,8]{1,0} convert(%ragged-dot-none.1)
  ROOT %tuple.3 = (bf16[64,8]{1,0}) tuple(%ragged-dot-none.1)
}
"""
    tables = program_tables(text)
    # an operand's path is not taken, an unnamed user is not walked past
    assert tables["scopes"]["ragged-dot-none.1"] == "ragged-dot-none"
    assert tables["renamed"] == {}


def test_a_module_without_compiler_named_kernels_keeps_its_table():
    """Names without a scope that the PROGRAM gave (arguments, the
    bodies of reductions, a Pallas kernel under its full path) are no
    compiler's: the table is PR 35's, key for key."""
    from horovod_tpu.telemetry.programs import program_tables

    text = """HloModule jit_prog, is_scheduled=true

%region_0.1 (reduce_sum.1: f32[], reduce_sum.2: f32[]) -> f32[] {
  %reduce_sum.1 = f32[] parameter(0), metadata={op_name="reduce_sum"}
  %reduce_sum.2 = f32[] parameter(1), metadata={op_name="reduce_sum"}
  ROOT %reduce_sum.3 = f32[] add(%reduce_sum.1, %reduce_sum.2), metadata={op_name="reduce_sum"}
}

%fused_computation (p: f32[8]) -> f32[] {
  %p = f32[8]{0} parameter(0)
  %constant.1 = f32[] constant(0)
  ROOT %reduce.1 = f32[] reduce(%p, %constant.1), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(prog)/hvd_step/loss_and_grad/jvp(f)/reduce_sum"}
}

ENTRY %main.7 (w: f32[8], batch_rows: f32[8]) -> (f32[], f32[8]) {
  %w = f32[8]{0} parameter(0), metadata={op_name="state['params']['w']"}
  %batch_rows.1 = f32[8]{0} parameter(1), metadata={op_name="batch_rows"}
  %copy.2 = f32[8]{0:S(1)} copy(%w), metadata={op_name="state['params']['w']"}
  %attn.3 = f32[8]{0} custom-call(%copy.2, %batch_rows.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(prog)/hvd_step/loss_and_grad/jvp(f)/attn/flash_fwd/flash_fwd"}
  %fusion.4 = f32[] fusion(%attn.3), kind=kInput, calls=%fused_computation
  %bitcast.5 = f32[8]{0} bitcast(%attn.3)
  ROOT %tuple.6 = (f32[], f32[8]{0}) tuple(%fusion.4, %bitcast.5)
}
"""
    flash = "jit(prog)/hvd_step/loss_and_grad/jvp(f)/attn/flash_fwd/flash_fwd"
    reduce = "jit(prog)/hvd_step/loss_and_grad/jvp(f)/reduce_sum"
    assert program_tables(text) == {
        "scopes": {
            "reduce_sum.1": "reduce_sum", "reduce_sum.2": "reduce_sum",
            "reduce_sum.3": "reduce_sum", "p": "", "constant.1": "",
            "reduce.1": reduce, "w": "state['params']['w']",
            "batch_rows.1": "batch_rows", "copy.2": "state['params']['w']",
            "attn.3": flash, "fusion.4": reduce, "bitcast.5": flash,
            "tuple.6": ""},
        "renamed": {}, "collectives": []}


_PSUM = "jit(prog)/shard_map/hvd_step/grad_reduce/psum"
_WV = "jit(prog)/shard_map/hvd_step/loss_and_grad/transpose(jvp(f))/" \
    "while/body/closed_call/checkpoint/layers/attn/wv/dot_general"
_COLLECTIVES_TEXT = f"""HloModule jit_prog, is_scheduled=true

%region_1.1 (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b)
}}

%fused_computation.1 (p0: f32[256,8]) -> (f32[256,8], f32[256,8], s32[2], u32[]) {{
  %p0 = f32[256,8]{{1,0}} parameter(0)
  %all-reduce.1 = f32[256,8]{{1,0}} all-reduce(%p0), channel_id=1, to_apply=%region_1.1, metadata={{op_name="{_PSUM}"}}
  ROOT %custom-call.1 = (f32[256,8]{{1,0}}, f32[256,8]{{1,0}}, s32[2]{{0}}, u32[]) custom-call(%all-reduce.1), custom_call_target="AsyncCollectiveStart"
}}

%async_collective_fusion.2 (p0: f32[256,8], p1: f32[256,8], p2: s32[2], p3: u32[], p4: bf16[256,64], p5: bf16[64,8]) -> (f32[256,8], f32[256,8], f32[256,8], s32[2], u32[]) {{
  %p0.1 = f32[256,8]{{1,0}} parameter(0)
  %p4 = bf16[256,64]{{1,0}} parameter(4)
  %p5 = bf16[64,8]{{1,0}} parameter(5)
  %convolution.2 = f32[256,8]{{1,0}} convolution(%p4, %p5), dim_labels=bf_io->bf, metadata={{op_name="{_WV}"}}
  %all-reduce.2 = f32[256,8]{{1,0}} all-reduce(%p0.1), channel_id=1, to_apply=%region_1.1, metadata={{op_name="{_PSUM}"}}
  ROOT %tuple.2 = (f32[256,8]{{1,0}}, f32[256,8]{{1,0}}, f32[256,8]{{1,0}}, s32[2]{{0}}, u32[]) tuple(%convolution.2, %p0.1, %all-reduce.2, %p2, %p3)
}}

%fused_computation.3 (p0: f32[256,8], p1: f32[256,8], p2: s32[2], p3: u32[]) -> f32[256,8] {{
  %p0.2 = f32[256,8]{{1,0}} parameter(0)
  %all-reduce.3 = f32[256,8]{{1,0}} all-reduce(%p0.2), channel_id=1, to_apply=%region_1.1, metadata={{op_name="{_PSUM}"}}
  ROOT %custom-call.3 = f32[256,8]{{1,0}} custom-call(%p0.2, %all-reduce.3), custom_call_target="AsyncCollectiveDone"
}}

ENTRY %main.20 (g: f32[256,8], n: f32[8], s: f32[], x: bf16[256,64], dy: bf16[64,8]) -> (f32[256,8], f32[256,8], f32[8], f32[]) {{
  %g = f32[256,8]{{1,0}} parameter(0)
  %n = f32[8]{{0}} parameter(1)
  %s = f32[] parameter(2)
  %x = bf16[256,64]{{1,0}} parameter(3)
  %dy = bf16[64,8]{{1,0}} parameter(4)
  %async-collective-start.4 = (f32[256,8]{{1,0}}, f32[256,8]{{1,0}}, s32[2]{{0}}, u32[]) fusion(%g), kind=kCustom, calls=%fused_computation.1
  %get-tuple-element.5 = f32[256,8]{{1,0}} get-tuple-element(%async-collective-start.4), index=0
  %get-tuple-element.6 = f32[256,8]{{1,0}} get-tuple-element(%async-collective-start.4), index=1
  %get-tuple-element.7 = s32[2]{{0}} get-tuple-element(%async-collective-start.4), index=2
  %get-tuple-element.8 = u32[] get-tuple-element(%async-collective-start.4), index=3
  %fusion.9 = (f32[256,8]{{1,0}}, f32[256,8]{{1,0}}, f32[256,8]{{1,0}}, s32[2]{{0}}, u32[]) fusion(%get-tuple-element.5, %get-tuple-element.6, %get-tuple-element.7, %get-tuple-element.8, %x, %dy), kind=kOutput, calls=%async_collective_fusion.2, metadata={{op_name="{_WV}"}}
  %get-tuple-element.10 = f32[256,8]{{1,0}} get-tuple-element(%fusion.9), index=0
  %get-tuple-element.11 = f32[256,8]{{1,0}} get-tuple-element(%fusion.9), index=1
  %get-tuple-element.12 = f32[256,8]{{1,0}} get-tuple-element(%fusion.9), index=2
  %get-tuple-element.13 = s32[2]{{0}} get-tuple-element(%fusion.9), index=3
  %get-tuple-element.14 = u32[] get-tuple-element(%fusion.9), index=4
  %all-reduce.15 = (f32[8]{{0}}, f32[]) all-reduce(%n, %s), channel_id=2, to_apply=%region_1.1, metadata={{op_name="{_PSUM}"}}
  %async-collective-done.4 = f32[256,8]{{1,0}} fusion(%get-tuple-element.11, %get-tuple-element.12, %get-tuple-element.13, %get-tuple-element.14), kind=kCustom, calls=%fused_computation.3, metadata={{op_name="{_PSUM}"}}
  %get-tuple-element.16 = f32[8]{{0}} get-tuple-element(%all-reduce.15), index=0
  %get-tuple-element.17 = f32[] get-tuple-element(%all-reduce.15), index=1
  ROOT %tuple.18 = (f32[256,8]{{1,0}}, f32[256,8]{{1,0}}, f32[8]{{0}}, f32[]) tuple(%async-collective-done.4, %get-tuple-element.10, %get-tuple-element.16, %get-tuple-element.17)
}}
"""


def test_collectives_table_on_hand_made_text():
    """A bare all-reduce is ``sync``; the TPU compiler's asynchronous
    one is a ``start`` and a ``done`` fusion whose pair is found along
    the state that a compute fusion carries between them, and that
    fusion is ``carried``; the all-reduces INSIDE fusions are no
    entries of their own."""
    from horovod_tpu.telemetry.programs import program_tables

    tables = program_tables(_COLLECTIVES_TEXT)
    nbytes = 256 * 8 * 4

    def entry(instruction, mode, pair, path, nbytes=nbytes):
        return {"instruction": instruction, "kind": "all-reduce",
                "bytes": nbytes, "mode": mode, "pair": pair, "path": path}

    assert tables["collectives"] == [
        entry("async-collective-start.4", "start",
              "async-collective-done.4", _PSUM),
        entry("fusion.9", "carried", None, _WV),
        entry("all-reduce.15", "sync", None, _PSUM, nbytes=8 * 4 + 4),
        entry("async-collective-done.4", "done",
              "async-collective-start.4", _PSUM)]
    assert all(entry["path"] == tables["scopes"][entry["instruction"]]
               for entry in tables["collectives"])
    assert tables["renamed"] == {}


def test_report_lists_a_two_rank_steps_psum(hvd_shutdown):
    """A real program: rank threads over two CPU devices run one
    ``shard_map`` step whose gradient (15 floats) and loss are summed
    across the ranks in one all-reduce."""
    import optax

    def fn():
        step = hvd.make_compiled_train_step(
            lambda p, b: ((b @ p["w"]) ** 2).mean(), optax.sgd(0.1))
        state = step.init_state({"w": np.ones((3, 5), np.float32)})
        state, _ = step(state, np.ones((2, 3), np.float32))
        return step.report(), telemetry.counter_total(
            telemetry.INIT_STATE_SECONDS_FAMILY), telemetry.counter_total(
            telemetry.INIT_SECONDS_FAMILY)

    for report, init_state_seconds, init_seconds in hvd.run(fn, np=2):
        assert report["renamed"] == {}
        reduced = [c for c in report["collectives"]
                   if c["kind"] == "all-reduce"]
        assert sum(c["bytes"] for c in reduced) == 15 * 4 + 4
        for c in reduced:
            assert c["mode"] == "sync" and c["pair"] is None
            assert "hvd_step/grad_reduce" in c["path"]
            assert report["scopes"][c["instruction"]] == c["path"]
        # start-up's two spans counted: once a process, and once a rank
        assert init_seconds > 0 and init_state_seconds > 0


def _recorded(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", name)
    with open(path) as f:
        return f.read()


def test_recorded_routed_layer_gets_its_phases_back():
    """One routed layer of SmallThinker's step as the chip's compiler
    left it (tests/data): every kernel lands under ``moe`` in the phase
    it runs in, 3 in the forward and 9 in the backward, and none claims
    a part of the layer it did not state.  (The recording is the
    reader's fixture, PR 36's program: today's backward runs 6.)"""
    from chipbench import scope_join, scope_time
    from horovod_tpu.telemetry.programs import program_tables

    tables = program_tables(_recorded("smallthinker_routed_layer.hlo.txt"))
    renamed, scopes = tables["renamed"], tables["scopes"]
    assert sorted(renamed.values()) == ["ragged-dot-metadata"] * 3 \
        + ["ragged-dot-none"] * 12
    part = re.compile(scope_time.component(
        "route", "dispatch", "combine", "experts"))
    phases = []
    for name, own in renamed.items():
        path = scopes[name]
        assert path.endswith("/moe/while/body/closed_call/" + own) \
            or path.endswith("/moe/while/body/" + own), path
        assert "/layer_3/" in path and not part.search(path), path
        if own == "ragged-dot-none":
            phases.append(scope_join.phase_of(path))
    assert sorted(phases) == ["backward"] * 9 + ["forward"] * 3
    # nothing else of the snippet was touched: what stated a path kept it
    assert all("/" in path for name, path in scopes.items()
               if name.startswith("fusion"))


def test_recorded_start_to_done_chains_pair_up():
    """Two asynchronous all-reduces of dp4's backward loop (tests/data):
    each done is paired with ITS start through the compute fusion that
    carries the state, which the shared suffix confirms."""
    from horovod_tpu.telemetry.programs import program_tables

    tables = program_tables(_recorded("dp4_async_allreduce_chain.hlo.txt"))
    found = {c["instruction"]: c for c in tables["collectives"]}
    assert {name: (c["mode"], c["pair"]) for name, c in found.items()} == {
        "all-reduce.121": ("sync", None),
        "async-collective-start": ("start", "async-collective-done"),
        "fusion.341": ("carried", None),
        "async-collective-done": ("done", "async-collective-start"),
        "async-collective-start.1": ("start", "async-collective-done.1"),
        "fusion.348": ("carried", None),
        "async-collective-done.1": ("done", "async-collective-start.1")}
    assert found["all-reduce.121"]["bytes"] == 2 * 4096 * 4
    assert all(c["bytes"] == 4096 * 8 * 128 * 4 for name, c in found.items()
               if name != "all-reduce.121")
    assert found["fusion.341"]["path"].endswith("layers/attn/wv/dot_general")
    assert all(found[name]["path"].endswith("hvd_step/grad_reduce/psum")
               for name in found if "collective" in name)
