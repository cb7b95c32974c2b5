"""Aux subsystem tests: timeline, data loader, stall inspector,
process-set dynamics, gated integrations."""

import json
import os
import time

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.data import AsyncDataLoaderMixin, BaseDataLoader


def test_timeline_records_ops(hvd_shutdown, tmp_path, monkeypatch):
    path = tmp_path / "timeline.json"
    monkeypatch.setenv("HOROVOD_TIMELINE", str(path))

    def fn():
        hvd.allreduce(np.ones(8, np.float32), name="tl_test")
        return True

    assert all(hvd.run(fn, np=4))
    hvd.shutdown()
    events = json.loads(path.read_text())
    names = {e.get("name") for e in events}
    assert "NEGOTIATE_ALLREDUCE" in names
    assert "ALLREDUCE" in names
    # lanes are named after tensors
    lanes = [e for e in events if e.get("ph") == "M"]
    assert any("tl_test" in str(e.get("args")) for e in lanes)


def test_timeline_records_algorithm(hvd_shutdown, tmp_path,
                                    monkeypatch):
    """The chosen reduction algorithm rides each negotiation entry's
    lane as an instant marker (flat / hierarchical / torus), without
    renaming the op events the reference's timeline tests assert."""
    path = tmp_path / "timeline_algo.json"
    monkeypatch.setenv("HOROVOD_TIMELINE", str(path))

    def fn():
        hvd.allreduce(np.ones(64, np.float32), name="tl_algo",
                      algorithm="torus")
        hvd.allreduce(np.ones(64, np.float32), name="tl_flat")
        return True

    assert all(hvd.run(fn, np=4))
    hvd.shutdown()
    events = json.loads(path.read_text())
    names = {e.get("name") for e in events}
    assert "ALGO_TORUS" in names, names
    assert "ALGO_FLAT" in names, names
    assert "ALLREDUCE" in names          # op names unchanged


def test_start_stop_timeline_runtime(hvd_shutdown, tmp_path):
    path = tmp_path / "tl2.json"

    def fn():
        hvd.allreduce(np.ones(2, np.float32), name="pre")
        return True

    hvd.init(num_ranks=2)
    hvd.start_timeline(str(path))
    hvd.run(fn, np=2)
    hvd.stop_timeline()
    hvd.shutdown()
    assert path.exists()
    events = json.loads(path.read_text())
    assert any(e.get("name") == "ALLREDUCE" for e in events)


class _Loader(AsyncDataLoaderMixin, BaseDataLoader):
    def __init__(self, n, **kw):
        self.n = n
        super().__init__(**kw)

    def __len__(self):
        return self.n

    def _iterate(self):
        for i in range(self.n):
            yield i * i


def test_async_data_loader():
    loader = _Loader(10, async_loading=True, queue_size=2)
    assert list(loader) == [i * i for i in range(10)]
    loader.close_async_loader()
    sync = _Loader(5, async_loading=False)
    assert list(sync) == [i * i for i in range(5)]


def test_stall_warning_names_ranks_and_rewarns(hvd_shutdown,
                                               monkeypatch, caplog):
    """Warning path of the stall inspector: the log names the missing
    GLOBAL rank ids, fires once per stall (dedup across cycles), and
    fires AGAIN when the same tensor name stalls a second time."""
    import logging
    import threading

    monkeypatch.setenv("HOROVOD_STALL_CHECK_TIME_SECONDS", "0.25")
    release = [threading.Event(), threading.Event()]

    def fn():
        for phase in range(2):
            if hvd.rank() == 0:
                # rank 0 holds back past the warning time, twice
                release[phase].wait(timeout=10)
            # same name on BOTH phases on purpose: the re-warn
            # contract is about re-used tensor names
            hvd.allreduce(np.ones(4, np.float32), name="stallw")
        return True

    def warnings():
        return [r for r in caplog.records
                if "stallw" in r.getMessage()
                and "stalled" in r.getMessage()]

    with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
        t = threading.Thread(
            target=lambda: hvd.run(fn, np=2, keep_alive=True),
            daemon=True)
        t.start()
        deadline = time.monotonic() + 10
        while not warnings() and time.monotonic() < deadline:
            time.sleep(0.05)
        first = warnings()
        assert first, "no stall warning before the deadline"
        msg = first[0].getMessage()
        # global attribution: rank 0 (a global rank id) is named
        assert "missing ranks: [0]" in msg, msg
        # once-per-stall dedup: the stall persists across many engine
        # cycles but warns exactly once
        time.sleep(0.5)
        assert len(warnings()) == 1, [r.getMessage()
                                      for r in warnings()]
        release[0].set()            # phase 1 completes
        # phase 2: the SAME tensor name stalls again -> second warning
        deadline = time.monotonic() + 10
        while len(warnings()) < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert len(warnings()) == 2, \
            "re-used tensor name did not re-warn on its second stall"
        release[1].set()
        t.join(timeout=10)
        assert not t.is_alive()
    # exported labels name the ranks too
    from horovod_tpu import telemetry
    assert telemetry.counter_total("horovod_stall_warnings_total",
                                   ranks="0") >= 2


def test_stall_mark_cleared_at_awaiting_completion_sites(hvd_shutdown):
    """Satellite fix for the _stall_warned leak: entries completing
    from ``awaiting`` (coordinator batch/error responses) must clear
    their warning mark, or a re-used name that stalls again warns only
    once per process lifetime."""
    from horovod_tpu.common import basics
    from horovod_tpu.core.engine import NegotiationEntry

    hvd.init(num_ranks=1)
    eng = basics.engine()
    ps = eng.get_process_set(0)
    key = "ALLREDUCE|leak|ps0"
    with eng._lock:
        ps.awaiting[key] = NegotiationEntry(key)
        eng._stall_warned.add((0, key))
    # completion through the coordinator-error path
    eng._apply_response({"kind": "error", "key": key, "message": "x"})
    assert (0, key) not in eng._stall_warned
    assert key not in ps.awaiting


def test_engine_applies_coordinator_stall_response(hvd_shutdown,
                                                   caplog):
    """A coordinator ``stall`` record warns once with the GLOBAL rank
    attribution and feeds the labeled stall-warning counter."""
    import logging

    from horovod_tpu import telemetry
    from horovod_tpu.common import basics

    hvd.init(num_ranks=1)
    eng = basics.engine()
    resp = {"kind": "stall", "key": "ALLREDUCE|g|ps0", "ps": 0,
            "age": 61.0, "missing_ranks": [3, 5],
            "missing_procs": [1]}
    with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
        eng._apply_response(resp)
        eng._apply_response(resp)       # duplicate: deduped
    msgs = [r.getMessage() for r in caplog.records
            if "missing global ranks" in r.getMessage()]
    assert len(msgs) == 1, msgs
    assert "[3, 5]" in msgs[0]
    assert telemetry.counter_total("horovod_stall_warnings_total",
                                   ranks="3,5") == 1


def test_coordinator_stall_attribution_two_procs():
    """Coordinator-side global stall attribution at 2 processes: the
    stall record names the global ranks of the process that never
    reported, once per stall, re-armed by completion."""
    from horovod_tpu.runner.http.http_server import Coordinator

    c = Coordinator(world_size=2, stall_warning_secs=0.1)

    def meta(key):
        return dict(key=key, type="ALLREDUCE", dtype="float32",
                    shape=[4], op=1, pre=1.0, post=1.0, ps=0,
                    nbytes=64, nprocs=2, nranks=4, root=-1,
                    members={"0": [0, 1], "1": [2, 3]}, aux={})

    c.handle("ready", {"proc": 0, "nlocal": 2,
                       "entries": [meta("s")]})
    time.sleep(0.15)
    out = c.handle("poll", {"cursor": 0, "wait": 0, "proc": 0})
    stalls = [r for r in out["responses"] if r["kind"] == "stall"]
    assert len(stalls) == 1
    assert stalls[0]["key"] == "s"
    assert stalls[0]["missing_ranks"] == [2, 3]     # global ranks
    assert stalls[0]["missing_procs"] == [1]
    # dedup while the same stall persists
    time.sleep(0.15)
    out = c.handle("poll", {"cursor": out["cursor"], "wait": 0,
                            "proc": 0})
    assert not [r for r in out["responses"] if r["kind"] == "stall"]
    # completion (proc 1 reports) re-arms; a second stall of the same
    # name warns again
    c.handle("ready", {"proc": 1, "nlocal": 2, "entries": [meta("s")],
                       "rid": 1})
    out = c.handle("poll", {"cursor": 0, "wait": 0, "proc": 0})
    assert [r for r in out["responses"] if r["kind"] == "batch"]
    c.handle("ready", {"proc": 0, "nlocal": 2, "entries": [meta("s")],
                       "rid": 2})
    time.sleep(0.15)
    out = c.handle("poll", {"cursor": out["cursor"], "wait": 0,
                            "proc": 0})
    stalls = [r for r in out["responses"] if r["kind"] == "stall"]
    assert len(stalls) == 1, "completion did not re-arm the stall mark"


def test_stall_inspector_errors_out(hvd_shutdown, monkeypatch):
    monkeypatch.setenv("HOROVOD_STALL_CHECK_TIME_SECONDS", "0.2")
    monkeypatch.setenv("HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", "0.5")

    def fn():
        if hvd.rank() == 0:
            # rank 0 never submits; others stall past shutdown time
            time.sleep(1.2)
            return "skipped"
        try:
            hvd.allreduce(np.ones(2, np.float32), name="stall")
            return "no error"
        except hvd.HorovodInternalError:
            return "stalled"

    out = hvd.run(fn, np=3)
    assert out[0] == "skipped"
    assert out[1] == out[2] == "stalled"


def test_log_level_env_honored_in_workers(hvd_shutdown, monkeypatch):
    """The runner exports HOROVOD_LOG_LEVEL / HOROVOD_LOG_HIDE_TIME
    (runner/config_parser.py); init() must configure the horovod_tpu
    logger from them, like the reference's logging.cc."""
    import logging

    monkeypatch.setenv("HOROVOD_LOG_LEVEL", "DEBUG")
    monkeypatch.setenv("HOROVOD_LOG_HIDE_TIME", "1")
    hvd.init(num_ranks=1)
    logger = logging.getLogger("horovod_tpu")
    assert logger.level == logging.DEBUG
    handlers = [h for h in logger.handlers
                if getattr(h, "_hvd_env_handler", False)]
    assert len(handlers) == 1
    assert "asctime" not in handlers[0].formatter._fmt
    # the logger owns its output now — no double-printing through the
    # host app's root handlers
    assert logger.propagate is False
    hvd.shutdown()

    # re-init with time shown: same handler, new format (idempotent)
    monkeypatch.setenv("HOROVOD_LOG_LEVEL", "ERROR")
    monkeypatch.delenv("HOROVOD_LOG_HIDE_TIME")
    hvd.init(num_ranks=1)
    assert logger.level == logging.ERROR
    handlers2 = [h for h in logger.handlers
                 if getattr(h, "_hvd_env_handler", False)]
    assert handlers2 == handlers        # no handler pile-up
    assert "asctime" in handlers[0].formatter._fmt
    # restore library defaults so later tests' caplog behavior is
    # unchanged
    logger.removeHandler(handlers[0])
    logger.setLevel(logging.NOTSET)
    logger.propagate = True


def test_dynamic_process_sets(hvd_shutdown):
    import threading
    barrier = threading.Barrier(4)

    def fn():
        r = hvd.rank()
        # every rank registers the same set (idempotent, SPMD style)
        evens = hvd.add_process_set(hvd.ProcessSet([0, 2]))
        if r in (0, 2):
            out = hvd.allreduce(np.ones(2, np.float32) * (r + 1),
                                op=hvd.Sum, name="ps_even",
                                process_set=evens)
            expected = 4.0      # ranks 0 and 2 -> (1 + 3)
            assert np.allclose(out, expected), out
        barrier.wait()
        # removal is collective (reference: add/remove must be called
        # by every process) — all ranks vote; the barrier inside
        # remove_process_set releases them together
        assert hvd.remove_process_set(evens)
        return True

    assert all(hvd.run(fn, np=4))


def test_spark_ray_gated():
    import horovod_tpu.spark as spark
    import horovod_tpu.ray as hvd_ray
    with pytest.raises(ImportError):
        spark.run(lambda: None)
    with pytest.raises(ImportError):
        hvd_ray.RayExecutor(num_workers=2)


def test_checkpoint_manager_sharded_roundtrip(tmp_path):
    """Sharded orbax checkpointing: save a pjit-sharded state, restore
    onto the same mesh with the same shardings (SURVEY §5.4 — beyond
    the reference's delegate-to-framework stance)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel import build_mesh
    from horovod_tpu.utils.checkpoint import CheckpointManager

    mesh = build_mesh(dp=4, tp=2)
    shd = NamedSharding(mesh, P("dp", "tp"))
    rep = NamedSharding(mesh, P())
    state = {
        "w": jax.device_put(
            jnp.arange(32, dtype=jnp.float32).reshape(8, 4), shd),
        "step": jax.device_put(jnp.int32(7), rep),
    }
    mgr = CheckpointManager(str(tmp_path / "ck"), max_to_keep=2)
    try:
        mgr.save(7, state)
        mgr.save(8, {"w": state["w"] + 1, "step": state["step"] + 1})
        assert mgr.all_steps() == [7, 8]
        out = mgr.restore(target=state,
                          shardings={"w": shd, "step": rep})
        assert out["w"].sharding == shd
        np.testing.assert_array_equal(np.asarray(out["w"] - 1),
                                      np.asarray(state["w"]))
        assert int(out["step"]) == 8
        # retention: saving a third drops the oldest
        mgr.save(9, state, force=True)
        assert 7 not in mgr.all_steps()
    finally:
        mgr.close()


def test_rank0_save_and_broadcast_restore(tmp_path, hvd_shutdown):
    import horovod_tpu as hvd
    from horovod_tpu.utils.checkpoint import (
        load_and_broadcast, save_rank0,
    )

    path = str(tmp_path / "state.pkl")

    def fn():
        state = {"weights": np.arange(4) * (hvd.rank() + 1),
                 "epoch": 3 + hvd.rank()}
        save_rank0(path, state)     # only rank 0's state lands
        hvd.barrier()
        restored = load_and_broadcast(path)
        return restored

    outs = hvd.run(fn, np=4)
    for o in outs:                  # every rank got rank 0's state
        np.testing.assert_array_equal(o["weights"], np.arange(4))
        assert o["epoch"] == 3


def test_profiler_trace_produces_xplane(tmp_path):
    """jax-profiler glue (SURVEY §5.1 device-side tracer): a traced
    region writes an XPlane dump; annotate() is a no-op outside."""
    import jax.numpy as jnp

    from horovod_tpu.utils import annotate, profile

    with annotate("outside-trace"):     # zero-overhead no-op path
        pass
    logdir = str(tmp_path / "prof")
    with profile(logdir):
        with annotate("compute"):
            x = jnp.arange(1024.0)
            (x * 2).block_until_ready()
    import glob
    dumps = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    assert dumps, f"no xplane dump under {logdir}"


def test_examples_and_benchmarks_compile():
    """Every shipped example/benchmark script must at least be valid
    Python against the current library surface (the reference smoke-
    runs its examples in CI; a full run needs frameworks/clusters this
    image lacks, but a stale import after a refactor must not ship)."""
    import compileall
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for target in ("examples", "benchmarks"):
        assert compileall.compile_dir(
            os.path.join(root, target), quiet=2, force=True), \
            f"{target}/ contains a script that does not compile"
    for script in ("chip_smoke.py", "__graft_entry__.py"):
        assert compileall.compile_file(
            os.path.join(root, script), quiet=2, force=True), \
            f"{script} does not compile"


@pytest.mark.parametrize("measured,rebased,tag,failed", [
    # a deterministic count drifting in EITHER direction; and, under a
    # baseline someone re-recorded low, the absolute floor still holds
    pytest.param({"wire_int8_reduction_vs_f32": 4.2,
                  "pair_f32_int4_cross_bytes": 900_000.0,
                  "moe_alltoall_int8_ratio": 3.7},
                 {"moe_alltoall_int8_ratio": 3.7}, "perf",
                 ["wire_int8_reduction_vs_f32",
                  "pair_f32_int4_cross_bytes",
                  "moe_alltoall_int8_ratio"], id="eq_drift_and_floor"),
    pytest.param({"moe_steady_recompiles": 1.0}, {}, "perf",
                 ["moe_steady_recompiles"], id="max_bound_zero"),
    pytest.param({"ckpt_async_anchored_frac": None}, {}, "perf",
                 ["ckpt_async_anchored_frac"], id="not_printed"),
    # faults never change what the wire moves: the same exact band
    pytest.param({"pair_bf16_int4_inner_bytes": 16_777_216.0,
                  "overlap_bitwise_parity": 0.0}, {}, "fault",
                 ["pair_bf16_int4_inner_bytes",
                  "overlap_bitwise_parity"], id="faulted_leg"),
])
def test_perf_gate_rules(measured, rebased, tag, failed):
    """``tools/perf_gate._gate`` against the checked-in baseline: the
    baseline passes against itself, and each rule catches its case."""
    from tools import perf_gate

    with open(perf_gate.BASELINE_PATH) as f:
        baseline = json.load(f)["metrics"]
    assert set(baseline) == set(perf_gate.METRICS)
    assert perf_gate._gate(baseline, baseline, tag=tag) == []
    measured = {k: v for k, v in {**baseline, **measured}.items()
                if v is not None}
    assert perf_gate._gate(measured, {**baseline, **rebased},
                           tag=tag) == failed


def test_data_service_remote_worker_and_shipped_fn():
    """Multi-host compute-cluster path: dispatcher with
    remote_workers=True, produce loop in another 'host' publishing
    over HTTP, dataset_fn shipped by the trainer
    (reference tensorflow/data/compute_worker.py flow)."""
    import threading

    from horovod_tpu.data.service import (
        DataServiceServer, data_service, run_remote_worker,
    )
    from horovod_tpu.tensorflow.data.compute_service import (
        _FN_KEY, _pickle_fn, _waiting_fn,
    )
    from horovod_tpu.runner.http.http_client import StoreClient

    server = DataServiceServer(None, num_workers=1,
                               remote_workers=True)
    config = server.start(0)
    try:
        client = StoreClient(config.addr, config.port,
                             bytes.fromhex(config.secret_hex))
        # trainer ships the dataset fn before/while workers wait
        client.put(_FN_KEY, _pickle_fn(
            lambda w, n: iter([{"w": w, "i": i} for i in range(3)])))

        stop = threading.Event()
        worker = threading.Thread(
            target=run_remote_worker,
            args=(config, 0,
                  _waiting_fn(None, client.get, stop.is_set, 10)),
            kwargs=dict(stop_event=stop), daemon=True)
        worker.start()

        got = list(data_service(config, rank=0, size=1, timeout=20))
        assert got == [{"w": 0, "i": i} for i in range(3)]
        worker.join(timeout=10)
        assert not worker.is_alive()
    finally:
        server.stop()


def test_compute_worker_fn_stop_without_dataset_fn():
    """A stopped service ends the dataset_fn wait loop instead of
    leaking a forever-polling thread."""
    import time

    from horovod_tpu.tensorflow.data.compute_service import (
        compute_worker_fn,
    )

    server, config = compute_worker_fn(num_workers=1)
    time.sleep(0.2)           # let the produce thread enter the wait
    server.stop()
    time.sleep(0.3)
    assert all(not t.is_alive() for t in server._threads)


def test_reference_task_and_driver_services():
    """The reference's TCP service stack end-to-end: driver
    registration by host hash, task command execution with captured
    output, exit codes, abort (reference
    runner/common/service/{driver,task}_service.py)."""
    import io
    import time

    from horovod_tpu.runner.common.service.driver_service import (
        BasicDriverClient, BasicDriverService,
    )
    from horovod_tpu.runner.common.service.task_service import (
        BasicTaskClient, BasicTaskService,
    )
    from horovod_tpu.runner.common.util import secret
    from horovod_tpu.runner.common.util.timeout import Timeout

    key = secret.make_secret_key()
    driver = BasicDriverService(2, "test driver service", key)
    tasks = [BasicTaskService(f"test task service #{i}", i, key)
             for i in range(2)]
    try:
        client = BasicDriverClient("test driver service",
                                   driver.addresses(), key)
        for i, t in enumerate(tasks):
            client.register_task(i, t.addresses(), f"hosthash-{i % 2}")
        driver.wait_for_initial_registration(Timeout(10, "{activity}"))
        assert sorted(driver.task_indices()) == [0, 1]
        assert driver.task_index_host_hash(0) == "hosthash-0"

        task_client = BasicTaskClient("test task service #0",
                                      tasks[0].addresses(), key)
        task_client.run_command("echo hello-from-task; exit 7",
                                env={}, capture_stdout=True)
        out = io.StringIO()
        stdout_t, _ = task_client.stream_command_output(stdout=out)
        exit_code = task_client.wait_for_command_exit_code(delay=0.1)
        assert exit_code == 7
        stdout_t.join(timeout=5)
        assert "hello-from-task" in out.getvalue()

        # second run_command is idempotent — same command result
        task_client.run_command("echo other", env={})
        terminated, code = task_client.command_result()
        assert terminated and code == 7
    finally:
        for t in tasks:
            t.shutdown()
        driver.shutdown()


def test_reference_compute_service_registration():
    """Dispatcher/worker registration + shutdown barrier (reference
    runner/common/service/compute_service.py)."""
    from horovod_tpu.runner.common.service.compute_service import (
        ComputeClient, ComputeService,
    )
    from horovod_tpu.runner.common.util import secret

    key = secret.make_secret_key()
    service = ComputeService(1, 2, key)
    try:
        client = ComputeClient(service.addresses(), key)
        client.register_dispatcher(0, "grpc://somewhere:1234")
        assert client.wait_for_dispatcher_registration(0, timeout=5) \
            == "grpc://somewhere:1234"
        with pytest.raises(IndexError):
            client.register_dispatcher(3, "grpc://bad:1")
        client.register_worker_for_dispatcher(0, worker_id=0)
        client.register_worker_for_dispatcher(0, worker_id=1)
        client.wait_for_dispatcher_worker_registration(0, timeout=5)
        client.shutdown()
        client.wait_for_shutdown()   # returns because shutdown was set
    finally:
        service.shutdown()


def test_runner_util_helpers():
    """runner.util + runner.common.util reference helpers behave."""
    import threading

    from horovod_tpu.runner.common.util.codec import (
        dumps_base64, loads_base64,
    )
    from horovod_tpu.runner.common.util.host_hash import host_hash
    from horovod_tpu.runner.common.util.hosts import (
        get_host_assignments, parse_hosts, parse_hosts_and_slots,
    )
    from horovod_tpu.runner.util.streams import Pipe
    from horovod_tpu.runner.util.threads import (
        execute_function_multithreaded, in_thread,
    )

    assert loads_base64(dumps_base64({"x": (1, 2)})) == {"x": (1, 2)}
    h1, h2 = host_hash(), host_hash(salt="other")
    assert h1 != h2 and "-" in h1

    names, slots = parse_hosts_and_slots("a:2,b:3")
    assert names == ["a", "b"] and slots == {"a": 2, "b": 3}
    alloc = get_host_assignments(parse_hosts("a:2,b:3"),
                                 2, max_num_proc=4)
    assert len(alloc) == 4  # capped by max, not total

    pipe = Pipe()
    got = []
    t = in_thread(lambda: got.append(pipe.read()))
    pipe.write("hello")
    t.join(timeout=5)
    assert got == ["hello"]
    pipe.close()
    assert pipe.read() is None

    results = execute_function_multithreaded(
        lambda a, b: a + b, [[1, 2], [3, 4], [5, 6]])
    assert results == {0: 3, 1: 7, 2: 11}


def test_elastic_reference_surface():
    """Elastic constants/settings/worker-notification TCP path."""
    import time

    from horovod_tpu.runner.common.util import secret
    from horovod_tpu.runner.elastic.constants import (
        RESET_LIMIT_EXCEEDED_MESSAGE,
    )
    from horovod_tpu.runner.elastic.settings import ElasticSettings
    from horovod_tpu.runner.elastic.worker import (
        HostUpdateResult, WorkerNotificationClient,
        WorkerNotificationManager, WorkerNotificationService,
    )

    assert "reset_limit" in RESET_LIMIT_EXCEEDED_MESSAGE
    s = ElasticSettings(discovery=None, min_num_proc=1,
                        max_num_proc=4, elastic_timeout=600,
                        reset_limit=3, num_proc=2)
    assert s.elastic and s.max_num_proc == 4

    manager = WorkerNotificationManager()
    seen = []

    class Listener:
        def on_hosts_updated(self, ts, res):
            seen.append((ts, res))

    manager.register_listener(Listener())
    key = secret.make_secret_key()
    service = WorkerNotificationService(key, None, manager)
    try:
        client = WorkerNotificationClient(service.addresses(), key)
        client.notify_hosts_updated(123.0, HostUpdateResult.added)
        deadline = time.monotonic() + 5
        while not seen and time.monotonic() < deadline:
            time.sleep(0.01)
        assert seen and seen[0][1] == HostUpdateResult.added
    finally:
        service.shutdown()
