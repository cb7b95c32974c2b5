"""Global runtime state and rank contexts.

TPU-native analogue of the reference's ``HorovodBasics``
(horovod/common/basics.py:29-340) + the C ABI topology queries
(operations.cc:932-1404).  Where the reference loads a shared library
and keeps per-*process* rank state, the TPU runtime keeps per-*rank
contexts* inside the host process: a TPU host drives all of its chips
from one process, so ranks are thread/SPMD positions bound to mesh
devices rather than one OS process per accelerator.
"""

import os
import threading
import time
from contextlib import contextmanager as _contextmanager

from . import env as env_mod
from .exceptions import HorovodInitError
from .topology import Topology

_state_lock = threading.RLock()
_engine = None
_topology = None
_timeline = None
_tls = threading.local()
_distributed_up = False
_elastic_round = 0
_metrics_server = None
_last_world_size = None


def _apply_platform_env(jax):
    """Re-assert JAX_PLATFORMS / JAX_NUM_CPU_DEVICES as config updates
    when backends are still uninitialized (see init())."""
    import os

    try:
        from jax._src import xla_bridge as _xb
        if _xb.backends_are_initialized():
            return
        plat = os.environ.get("JAX_PLATFORMS")
        if plat and jax.config.jax_platforms != plat:
            jax.config.update("jax_platforms", plat)
        ncpu = os.environ.get("JAX_NUM_CPU_DEVICES")
        if ncpu:
            # wins over an inherited XLA_FLAGS device count (e.g. a
            # parent test process forcing 8 devices): the launcher's
            # count is the contract
            jax.config.update("jax_num_cpu_devices", int(ncpu))
    except Exception:  # noqa: BLE001 — best effort: private API moved
        # or malformed env value; init proceeds with whatever jax
        # resolves from env alone
        return


def _elastic_rendezvous(rdv_addr, rdv_port, secret):
    """Fetch this worker's rank/size/coordinator for the next elastic
    round from the launcher's KV store (reference: rank/size re-fetched
    from the rendezvous server on every reset,
    gloo_context.cc:168-206)."""
    import json
    import time as _time
    from ..runner.http.http_client import StoreClient

    global _elastic_round
    client = StoreClient(rdv_addr, rdv_port, secret)
    identity = (f"{env_mod.get_str(env_mod.HOROVOD_HOSTNAME, 'localhost')}"
                f":{env_mod.get_int(env_mod.HOROVOD_LOCAL_RANK, 0)}")
    deadline = _time.monotonic() + env_mod.get_float(
        "HOROVOD_ELASTIC_TIMEOUT", 600.0)
    while _time.monotonic() < deadline:
        raw = client.get("/elastic/round", wait=10.0)
        if raw is None:
            continue
        info = json.loads(raw)
        if info.get("suspended") and info["round"] > _elastic_round:
            # the fleet controller preempted this job to zero
            # (docs/fleet.md "Suspension"): the last commit is in the
            # spill and the control plane stays up — a worker that
            # outlives its job's suspension self-aborts CLEANLY so the
            # driver's drain grace never has to SIGTERM it, and the
            # resumed round restores committed state in fresh workers
            import logging as _logging
            _logging.getLogger("horovod_tpu").warning(
                "job suspended at round %d; exiting cleanly (state "
                "committed to the spill)", info["round"])
            raise SystemExit(0)
        if info["round"] <= _elastic_round:
            _time.sleep(0.2)
            continue
        if identity not in info["assignments"]:
            # not part of this round (e.g. blacklisted); keep waiting —
            # the driver terminates us if we stay unassigned
            _time.sleep(0.5)
            continue
        _elastic_round = info["round"]
        # round-formation marker: the driver's elastic_timeout watches
        # these to distinguish a forming round from a stuck one
        client.put(f"/elastic/joined/{info['round']}/"
                   f"{info['assignments'][identity]}", b"1")
        return (info["assignments"][identity], info["size"],
                info["coordinator"], info["round"])
    raise HorovodInitError("timed out waiting for elastic rendezvous")


class RankContext:
    """Per-rank identity + auto-naming counters.  The reference names
    unnamed ops by a per-process incrementing id
    (e.g. allreduce.noname.1); here the counter is per rank context."""

    def __init__(self, rank):
        self.rank = rank
        self._counters = {}

    def next_name(self, op_name):
        n = self._counters.get(op_name, 0) + 1
        self._counters[op_name] = n
        return f"{op_name}.noname.{n}"


def _make_timeline(config, pid=0, num_ranks=1, proc_id=0):
    """Per-process timeline.  With ``HOROVOD_TIMELINE`` it writes a
    Chrome trace file; without one it still runs ring-only when the
    flight recorder is enabled (``HOROVOD_TRACE_RING_EVENTS``, default
    on) so stall warnings always have a last-N-events trace to dump.
    ``pid`` is the process's first global rank — merged traces key one
    lane group per rank on it (docs/timeline.md)."""
    from ..utils.timeline import Timeline
    if not (config.timeline_filename or config.trace_ring_events > 0):
        return None
    if num_ranks > 1:
        pname = (f"ranks {pid}-{pid + num_ranks - 1} "
                 f"(proc {proc_id})")
    else:
        pname = f"rank {pid}"
    return Timeline(config.timeline_filename,
                    config.timeline_mark_cycles,
                    pid=pid, process_name=pname,
                    ring_events=config.trace_ring_events)


def _record_resize_event(new_size):
    """Elastic membership change → telemetry.  Called AFTER the engine
    installed the round's fresh registry; ``_last_world_size``
    survives shutdown/init cycles so the direction is the true delta
    across rounds."""
    global _last_world_size
    from .. import telemetry

    prev, _last_world_size = _last_world_size, new_size
    if prev is None or prev == new_size:
        direction = "initial" if prev is None else "rebalance"
    else:
        direction = "up" if new_size > prev else "down"
    telemetry.registry().counter(
        telemetry.ELASTIC_RESIZE_FAMILY,
        telemetry.ELASTIC_RESIZE_HELP,
        labelnames=("direction",)).labels(direction=direction).inc()


def _start_metrics_endpoint(config, proc_index):
    """Per-worker Prometheus endpoint (HOROVOD_METRICS_PORT /
    ``horovodrun --metrics-port``).  Workers sharing a host offset the
    base port by their process index so every endpoint binds."""
    global _metrics_server
    if config.metrics_port <= 0 or _metrics_server is not None:
        return
    from ..telemetry import MetricsServer
    port = config.metrics_port + (proc_index or 0)
    server = MetricsServer(port=port)
    try:
        server.start()
    except OSError as exc:
        import logging
        logging.getLogger("horovod_tpu").warning(
            "could not bind metrics endpoint on port %d: %s "
            "(metrics still available via hvd.metrics() and the "
            "coordinator's /metrics)", port, exc)
        return
    _metrics_server = server


def init(comm=None, process_sets=None, num_ranks=None, devices=None):
    """Initialize the runtime (reference horovod_init,
    operations.cc:934 → InitializeHorovodOnce :856).

    * ``num_ranks`` — number of ranks this process hosts.  Defaults to
      ``HOROVOD_TPU_RANKS_PER_PROC`` (set by the launcher) or 1.
    * ``comm`` — list of global ranks (subset init), kept for API
      parity; MPI communicators are not a TPU concept.
    * ``process_sets`` — list of ProcessSet objects to register at
      init time (reference basics.py:51-148).

    Under the multi-process launcher (``HOROVOD_CONTROLLER=http``,
    reference gloo_run.py:66-103 env handoff), this also brings up
    ``jax.distributed`` so compiled collectives span processes, and a
    :class:`StoreController` for negotiation (reference
    GlooContext::Initialize, gloo/gloo_context.cc:150-216).

    The call is one host span, ``hvd: init``, and its seconds land in
    ``horovod_init_seconds_total`` of the registry it installs.
    """
    from .. import telemetry
    from ..utils import profiler

    t0 = time.perf_counter()
    with profiler.annotate("hvd: init"):
        _init(comm, process_sets, num_ranks, devices)
    # the engine has just installed this round's registry: the span's
    # counter is written here and not through ``annotate``
    telemetry.registry().counter(
        telemetry.INIT_SECONDS_FAMILY, telemetry.INIT_SECONDS_HELP).inc(
        time.perf_counter() - t0)


def _init(comm, process_sets, num_ranks, devices):
    global _engine, _topology, _timeline
    with _state_lock:
        if _engine is not None:
            # Reference allows repeated init as a no-op once running.
            _bind_thread_if_unbound()
            return
        from ..core.engine import Engine

        # honor the runner's HOROVOD_LOG_LEVEL / HOROVOD_LOG_HIDE_TIME
        # handoff before anything logs (reference logging.cc reads the
        # same env in every worker)
        env_mod.setup_logging()

        controller = None
        rank_offset = 0
        global_size = None
        ranks_of_proc = None
        proc_index = 0
        multiproc = env_mod.get_str(env_mod.HOROVOD_CONTROLLER) == "http"
        if num_ranks is None:
            num_ranks = env_mod.get_int(env_mod.HOROVOD_TPU_RANKS_PER_PROC, 0)
        if not num_ranks:
            num_ranks = 1
        if multiproc:
            from ..core.store_controller import StoreController
            import jax

            # Honor the launcher's platform contract programmatically:
            # site configs (e.g. a preloaded PJRT plugin) can override
            # the JAX_PLATFORMS env var by force-setting the config at
            # interpreter start, which would leave every worker on the
            # wrong backend and break the global device view.  Only
            # possible before first backend use.
            _apply_platform_env(jax)

            rdv_addr = env_mod.get_str(env_mod.HOROVOD_RENDEZVOUS_ADDR,
                                       "127.0.0.1")
            rdv_port = env_mod.get_int(env_mod.HOROVOD_RENDEZVOUS_PORT, 0)
            secret = env_mod.get_str("HOROVOD_SECRET_KEY")
            secret = bytes.fromhex(secret) if secret else None
            round_id = 0
            if env_mod.get_bool("HOROVOD_ELASTIC"):
                proc_id, num_procs, coordinator, round_id = \
                    _elastic_rendezvous(rdv_addr, rdv_port, secret)
            else:
                proc_id = env_mod.get_int(env_mod.HOROVOD_TPU_PROC_INDEX, 0)
                num_procs = env_mod.get_int(env_mod.HOROVOD_TPU_NUM_PROCS, 1)
                coordinator = env_mod.get_str(
                    env_mod.HOROVOD_TPU_COORDINATOR)
            if num_procs > 1 and coordinator:
                # the TFRT CPU client can't launch cross-process
                # computations without a collectives transport; jax's
                # gloo implementation makes the virtual CPU mesh behave
                # like a real multi-host TPU slice.  Must be set before
                # the backends initialize.
                if jax.config.jax_platforms in ("cpu", None) or \
                        env_mod.get_str(
                            env_mod.HOROVOD_TPU_PLATFORM) == "cpu":
                    jax.config.update(
                        "jax_cpu_collectives_implementation", "gloo")
                jax.distributed.initialize(
                    coordinator_address=coordinator,
                    num_processes=num_procs, process_id=proc_id,
                    initialization_timeout=env_mod.get_int(
                        "HOROVOD_TPU_INIT_TIMEOUT", 60))
                global _distributed_up
                _distributed_up = True
            else:
                # size-1 round after an IN-PROCESS elastic resize: a
                # sticky gloo collectives flag from the previous
                # multi-proc round would make the fresh CPU backend
                # demand a distributed client that no longer exists
                # (make_gloo_tcp_collectives(None) TypeError) — reset
                # it before first backend use
                if jax.config.jax_cpu_collectives_implementation \
                        == "gloo":
                    jax.config.update(
                        "jax_cpu_collectives_implementation", None)
            # heterogeneous host:slots jobs (reference -H h1:4,h2:2,
            # gloo_run.py:66-103) carry per-process rank counts; the
            # uniform path is the table [num_ranks] * num_procs
            rop = env_mod.get_str("HOROVOD_TPU_RANKS_OF_PROC")
            ranks_of_proc = None
            if rop:
                ranks_of_proc = [int(x) for x in rop.split(",")]
                if len(ranks_of_proc) != num_procs:
                    raise HorovodInitError(
                        f"HOROVOD_TPU_RANKS_OF_PROC has "
                        f"{len(ranks_of_proc)} entries for "
                        f"{num_procs} processes (stale environment?)")
                num_ranks = ranks_of_proc[proc_id]
                global_size = sum(ranks_of_proc)
                rank_offset = sum(ranks_of_proc[:proc_id])
            else:
                global_size = num_procs * num_ranks
                rank_offset = proc_id * num_ranks
            proc_index = proc_id
            if devices is None:
                import jax as _jax
                devices = _jax.devices()
            if len(devices) < global_size:
                raise HorovodInitError(
                    f"multi-process mode needs one device per rank: "
                    f"{len(devices)} devices < {global_size} ranks")
            hof = env_mod.get_str("HOROVOD_TPU_HOST_OF_RANK")
            counts = ranks_of_proc or [num_ranks] * num_procs
            if hof:
                # launcher's true host layout (one entry per process):
                # multiple processes on one host share local_rank space
                host_of_proc = [int(x) for x in hof.split(",")]
                if len(host_of_proc) != num_procs:
                    raise HorovodInitError(
                        f"HOROVOD_TPU_HOST_OF_RANK has "
                        f"{len(host_of_proc)} entries for {num_procs} "
                        f"processes (stale environment?)")
            else:
                host_of_proc = list(range(num_procs))
            host_of_rank = [host_of_proc[p]
                            for p in range(num_procs)
                            for _ in range(counts[p])]
            _topology = Topology(size=global_size,
                                 host_of_rank=host_of_rank)
            # per-host aggregator tier (docs/fault_tolerance.md): the
            # lowest-indexed proc of each host starts the aggregator
            # and publishes its address in the launcher's KV store;
            # every local proc routes its control traffic through it
            # (TieredStoreClient keeps the direct coordinator route
            # as the fallback)
            agg_addr = agg_port = None
            from ..runner.http import aggregator as agg_mod
            if agg_mod.tier_enabled() and num_procs > 1:
                agg_addr, agg_port, _agg_id = \
                    agg_mod.ensure_host_aggregator(
                        rdv_addr, rdv_port, secret, proc_id,
                        host_of_proc, round_id=round_id)
            controller = StoreController(
                rdv_addr, rdv_port, secret, proc_id, num_procs,
                num_ranks, round_id=round_id,
                agg_addr=agg_addr, agg_port=agg_port)
        else:
            _topology = Topology(size=num_ranks)
        if devices is None:
            import jax
            platform = env_mod.get_str(env_mod.HOROVOD_TPU_PLATFORM)
            devices = jax.devices(platform) if platform else jax.devices()
        config = env_mod.Config()
        # chaos fault injection (docs/fault_tolerance.md): parse the
        # plan BEFORE the engine exists so request-count triggers see
        # every fabric request, and hook the injector into the
        # controller's client (wire faults) + the engine (slow-rank).
        # A malformed plan raises here — a chaos test whose faults
        # silently failed to install would pass vacuously.
        chaos_injector = None
        if config.fault_plan:
            from .. import chaos as chaos_mod
            plan = chaos_mod.plan_from_env()
            if plan is not None and plan.events:
                chaos_injector = chaos_mod.install(
                    plan,
                    proc=controller.proc_id if controller else 0,
                    rank_offset=rank_offset,
                    num_local=num_ranks,
                    client=controller.client if controller else None)
        # each process records its own local ranks; the rank-0 process
        # keeps the user's HOROVOD_TIMELINE path (reference
        # docs/timeline.rst names rank 0's file) and the others write
        # suffixed siblings — same-path clobbering on a shared
        # filesystem would otherwise corrupt the trace
        if config.timeline_filename and rank_offset != 0:
            root, ext = os.path.splitext(config.timeline_filename)
            config.timeline_filename = f"{root}.proc{proc_id}{ext}"
        _timeline = _make_timeline(config, pid=rank_offset,
                                   num_ranks=num_ranks,
                                   proc_id=proc_index)
        _engine = Engine(num_ranks, devices, config=config,
                         topology=_topology, timeline=_timeline,
                         controller=controller, rank_offset=rank_offset,
                         global_size=global_size,
                         ranks_of_proc=ranks_of_proc,
                         chaos=chaos_injector)
        # telemetry surface: per-worker exposition endpoint + elastic
        # resize accounting (the engine just installed this round's
        # fresh registry)
        _start_metrics_endpoint(config, proc_index)
        if env_mod.get_bool(env_mod.HOROVOD_ELASTIC):
            _record_resize_event(_engine.global_size)
        if process_sets:
            from . import process_sets as ps_mod
            for ps in process_sets:
                ps_mod._register(ps)
        _bind_thread_if_unbound()


def _bind_thread_if_unbound():
    if getattr(_tls, "ctx", None) is None and _engine is not None:
        if _engine.num_local == 1:
            _tls.ctx = RankContext(_engine.rank_offset)


def bind_rank(rank):
    """Bind the calling thread to a rank context.  ``rank`` is the
    LOCAL rank index within this process (0..num_local); the context
    carries the global rank.  Used by the thread launcher (one thread
    per rank) and by tests."""
    if _engine is None:
        raise HorovodInitError("horovod_tpu.init() has not been called")
    if rank < 0 or rank >= _engine.num_local:
        raise ValueError(
            f"local rank {rank} out of range [0, {_engine.num_local})")
    _tls.ctx = RankContext(_engine.rank_offset + rank)
    return _tls.ctx


def unbind_rank():
    _tls.ctx = None


@_contextmanager
def bound_context(ctx):
    """Temporarily bind ``ctx`` (a RankContext) to the calling thread.
    Frameworks that run callbacks on their own pool threads (e.g. TF's
    py_function executor) use this to carry the submitting rank's
    identity across the thread hop."""
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ctx
    try:
        yield ctx
    finally:
        _tls.ctx = prev


def context() -> RankContext:
    ctx = getattr(_tls, "ctx", None)
    if ctx is None:
        if _engine is None:
            raise HorovodInitError(
                "horovod_tpu has not been initialized; call init() first")
        _bind_thread_if_unbound()
        ctx = getattr(_tls, "ctx", None)
        if ctx is None:
            raise HorovodInitError(
                "this thread is not bound to a rank; use horovod_tpu.run() "
                "or bind_rank()")
    return ctx


def engine():
    if _engine is None:
        raise HorovodInitError(
            "horovod_tpu has not been initialized; call init() first")
    return _engine


def is_initialized():
    return _engine is not None


def needs_exec_restart():
    """True when recovery requires a fresh process: the runtime aborted
    (peer death / stale round) while jax.distributed was live — the
    coordination client cannot be cleanly re-initialized in-process
    and will fatally terminate us on its next heartbeat."""
    return _engine is not None and _engine._aborted is not None \
        and _distributed_up


#: set by shutdown() when the clean-teardown coordination barrier
#: timed out (a peer never arrived): the abandoned client makes
#: in-process re-init unsafe
_teardown_wedged = False


def take_teardown_wedged():
    """True (once) when the last shutdown() abandoned its coordination
    barrier — the elastic reset must exec-restart instead of
    re-initializing in-process (docs/fault_tolerance.md)."""
    global _teardown_wedged
    wedged, _teardown_wedged = _teardown_wedged, False
    return wedged


def shutdown():
    """Reference horovod_shutdown (operations.cc:966).  In
    multi-process mode also tears down jax.distributed and clears the
    cached XLA backends so a later init() can re-form the mesh with a
    different world (elastic re-rendezvous, SURVEY §7.7)."""
    global _engine, _topology, _timeline, _distributed_up
    with _state_lock:
        if _engine is None:
            return
        _engine.shutdown()
        if _timeline is not None:
            _timeline.close()
        if _engine.multiproc:
            # stop this process's per-host aggregator (if it owns
            # one) AFTER the engine's goodbye rode it; co-hosted
            # workers still running fall back to direct mode
            from ..runner.http.aggregator import \
                stop_process_aggregator
            stop_process_aggregator()
        from . import process_sets as ps_mod
        ps_mod._reset()
        from ..ops import compiled as _compiled
        _compiled.reset_compiled_state()
        was_multiproc = _engine.multiproc
        was_aborted = _engine._aborted is not None
        _engine = None
        _topology = None
        _timeline = None
        _tls.ctx = None
        if _distributed_up:
            if not was_aborted:
                # clean teardown: every peer participates in the
                # coordination-service shutdown barrier — but BOUNDED.
                # A peer wedged in a data-plane collective (an armed
                # bypass vote racing a graceful resize: its agreement
                # allreduce blocks on us while we block on its
                # barrier) can never arrive; waiting forever would
                # deadlock the whole job.  On timeout, abandon the
                # barrier thread and flag the teardown wedged — the
                # coordination client is in an unknown state, so the
                # elastic reset exec-restarts this worker into the
                # next round (take_teardown_wedged).
                import threading as _threading
                import jax

                done = _threading.Event()

                def _barrier():
                    try:
                        jax.distributed.shutdown()
                    except Exception:  # noqa: BLE001 — peers gone
                        pass
                    done.set()

                _threading.Thread(target=_barrier, daemon=True,
                                  name="hvd-dist-shutdown").start()
                budget = env_mod.get_float(
                    env_mod.HOROVOD_TEARDOWN_BARRIER_SECONDS, 10.0)
                if not done.wait(budget):
                    global _teardown_wedged
                    _teardown_wedged = True
                    import logging as _logging
                    _logging.getLogger("horovod_tpu").warning(
                        "coordination shutdown barrier did not "
                        "complete within %.1fs (a peer is wedged in "
                        "a data-plane collective?); abandoning it — "
                        "this worker will exec-restart into the next "
                        "round", budget)
            # aborted: a peer is dead — the shutdown barrier would
            # LOG(FATAL) this process.  Leave the client; the elastic
            # loop exec-restarts the process instead (see
            # elastic.run / needs_exec_restart).
            _distributed_up = False
        if was_multiproc:
            # clear cached XLA backends even when this round ran
            # single-process (size-1 elastic rounds): the next round may
            # need jax.distributed.initialize, which requires no live
            # backend
            try:
                import jax.extend.backend as _xb
                _xb.clear_backends()
            except Exception:  # noqa: BLE001
                pass


# -- topology queries (reference operations.cc:996-1075) -----------------------

def rank():
    return context().rank


def size():
    return engine().num_ranks


def local_rank():
    return engine().topology.local_rank(rank())


def local_size():
    return engine().topology.local_size(rank())


def cross_rank():
    return engine().topology.cross_rank(rank())


def cross_size():
    return engine().topology.cross_size(rank())


def is_homogeneous():
    return engine().topology.is_homogeneous()


# -- build-feature queries (reference basics.py:250-340).  The TPU
#    runtime has exactly one data plane: compiled XLA collectives. ------------

def mpi_threads_supported():
    return False


def mpi_built():
    return False


def gloo_built():
    return False


def nccl_built():
    return False


def ddl_built():
    return False


def ccl_built():
    return False


def cuda_built():
    return False


def rocm_built():
    return False


def xla_built():
    return True


def tpu_built():
    return True


def mpi_enabled():
    """Whether the MPI controller drives negotiation (reference
    mpi_ops ``mpi_enabled``).  Never on TPU — the store controller
    fills that role."""
    return False


def gloo_enabled():
    """Whether the gloo-style control plane is active (reference
    mpi_ops ``gloo_enabled``).  Always True: the HMAC-HTTP store
    controller (core/store_controller.py) fills the gloo controller's
    role on every launch path, including elastic.  Note
    ``gloo_built()`` stays False — no libgloo is linked."""
    return True


def metrics():
    """Snapshot of this process's metric registry — a JSON-able dict
    keyed by family name (docs/observability.md).  The programmatic
    twin of the ``/metrics.json`` endpoint; works before init() too
    (empty registry)."""
    from .. import telemetry
    return telemetry.metrics()


def start_metrics_server(port=None):
    """Start (or return) this worker's Prometheus endpoint.  With no
    argument uses ``HOROVOD_METRICS_PORT`` (+ process index); an
    explicit ``port`` binds exactly there.  Returns the server object
    (``.port`` is the bound port — pass ``port=0`` for an ephemeral
    one)."""
    global _metrics_server
    with _state_lock:
        if port is None:
            if _metrics_server is not None:
                return _metrics_server
            from . import env as env_mod_
            port = env_mod_.get_int(env_mod_.HOROVOD_METRICS_PORT, 0)
            if port:
                port += env_mod_.get_int(
                    env_mod_.HOROVOD_TPU_PROC_INDEX, 0)
        from ..telemetry import MetricsServer
        server = MetricsServer(port=port or 0)
        server.start()
        if _metrics_server is None:
            _metrics_server = server
        return server


def start_timeline(filename, mark_cycles=False):
    """Runtime timeline activation (reference operations.cc:1077).
    A ring-only flight-recorder timeline (no file) is upgraded in
    place; an already-writing file timeline must be stopped first."""
    global _timeline
    with _state_lock:
        eng = engine()
        if _timeline is not None and _timeline.filename:
            raise ValueError("timeline already active; stop it first")
        from ..utils.timeline import Timeline
        old, pid, pname = _timeline, eng.rank_offset, None
        if old is not None:
            pid, pname = old.pid, old.process_name
        _timeline = Timeline(filename, mark_cycles, pid=pid,
                             process_name=pname,
                             ring_events=eng.config.trace_ring_events)
        eng.timeline = _timeline
        # a job initialized with tracing fully off (ring disabled, no
        # HOROVOD_TIMELINE) had no clock sync to start; the first
        # runtime-activated timeline needs it for mergeable traces
        eng._start_clock_sync()
        if old is not None:
            old.close()


def stop_timeline():
    """Stop writing the timeline file.  The flight recorder stays
    live (a fresh ring-only timeline replaces the file writer) so
    stall auto-dumps and ``hvd.dump_trace()`` keep working."""
    global _timeline
    with _state_lock:
        eng = engine()
        old = _timeline
        eng.config.timeline_filename = None
        _timeline = _make_timeline(
            eng.config, pid=eng.rank_offset, num_ranks=eng.num_local,
            proc_id=eng.controller.proc_id if eng.multiproc else 0)
        eng.timeline = _timeline
        if old is not None:
            old.close()


def dump_trace(path=None):
    """Dump the flight recorder's last-N-events ring NOW: pushes it to
    the launcher over the KV fabric (multi-process — the buffers
    ``GET /timeline`` merges) and writes a stand-alone Chrome trace to
    ``path`` (or ``HOROVOD_TRACE_DUMP_DIR``) when given.  Returns the
    file path written, or None (docs/timeline.md "Flight recorder")."""
    return engine().dump_trace(path=path, reason="manual")


# -- reference-shaped surface (horovod/common/basics.py:21-29) ---------------

class MPI:
    """Typing stand-in matching the reference's lazy mpi4py shim
    (reference basics.py:21-23) — there is no MPI on TPU pods, so
    ``MPI.Comm`` only exists for signature compatibility."""

    class Comm:
        ...


class HorovodBasics:
    """Object-shaped view of this module (reference basics.py:29
    wraps the C library in a class; frontends hold an instance).
    Every method delegates to the module-level implementation, so
    ``HorovodBasics().rank()`` and ``basics.rank()`` are the same."""

    def __init__(self, pkg_path=None, *args):
        # the reference dlopen()s the compiled extension here; this
        # runtime is pure Python so the arguments are accepted and
        # ignored
        self.MPI_LIB_CTYPES = None

    def __getattr__(self, name):
        import sys
        mod = sys.modules[__name__]
        try:
            return getattr(mod, name)
        except AttributeError:
            raise AttributeError(
                f"'HorovodBasics' object has no attribute '{name}'")
