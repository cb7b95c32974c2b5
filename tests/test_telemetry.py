"""Telemetry subsystem tests: registry semantics, Prometheus
exposition, snapshot aggregation, the per-worker HTTP endpoint, the
coordinator's job-wide /metrics, and the engine's family catalogue."""

import json
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
