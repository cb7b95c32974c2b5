"""Environment-variable configuration surface.

The reference uses ~40 ``HOROVOD_*`` env vars as the ABI between the
launcher and the core runtime (reference common/common.h:115-149, parsed
in operations.cc:459-650 and utils/env_parser.cc).  We keep the same
names so launcher flags, config files and user habits carry over.
"""

import logging
import os

# --- knob names (reference common.h:115-149) ---------------------------------
HOROVOD_FUSION_THRESHOLD = "HOROVOD_FUSION_THRESHOLD"
HOROVOD_CYCLE_TIME = "HOROVOD_CYCLE_TIME"
HOROVOD_CACHE_CAPACITY = "HOROVOD_CACHE_CAPACITY"
HOROVOD_TIMELINE = "HOROVOD_TIMELINE"
HOROVOD_TIMELINE_MARK_CYCLES = "HOROVOD_TIMELINE_MARK_CYCLES"
HOROVOD_AUTOTUNE = "HOROVOD_AUTOTUNE"
HOROVOD_AUTOTUNE_LOG = "HOROVOD_AUTOTUNE_LOG"
HOROVOD_AUTOTUNE_WARMUP_SAMPLES = "HOROVOD_AUTOTUNE_WARMUP_SAMPLES"
HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE = "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE"
HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES = "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"
HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE = "HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE"
HOROVOD_STALL_CHECK_DISABLE = "HOROVOD_STALL_CHECK_DISABLE"
HOROVOD_STALL_CHECK_TIME_SECONDS = "HOROVOD_STALL_CHECK_TIME_SECONDS"
HOROVOD_STALL_SHUTDOWN_TIME_SECONDS = "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"
HOROVOD_HIERARCHICAL_ALLREDUCE = "HOROVOD_HIERARCHICAL_ALLREDUCE"
HOROVOD_HIERARCHICAL_ALLGATHER = "HOROVOD_HIERARCHICAL_ALLGATHER"
HOROVOD_TORUS_ALLREDUCE = "HOROVOD_TORUS_ALLREDUCE"
HOROVOD_ELASTIC = "HOROVOD_ELASTIC"
HOROVOD_PROCESS_SET_REMOVAL_TIMEOUT = "HOROVOD_PROCESS_SET_REMOVAL_TIMEOUT"
HOROVOD_LOG_LEVEL = "HOROVOD_LOG_LEVEL"
HOROVOD_LOG_HIDE_TIME = "HOROVOD_LOG_HIDE_TIME"

# rank/topology handoff from the launcher (reference gloo_run.py:66-103)
HOROVOD_RANK = "HOROVOD_RANK"
HOROVOD_SIZE = "HOROVOD_SIZE"
HOROVOD_LOCAL_RANK = "HOROVOD_LOCAL_RANK"
HOROVOD_LOCAL_SIZE = "HOROVOD_LOCAL_SIZE"
HOROVOD_CROSS_RANK = "HOROVOD_CROSS_RANK"
HOROVOD_CROSS_SIZE = "HOROVOD_CROSS_SIZE"
HOROVOD_HOSTNAME = "HOROVOD_HOSTNAME"
HOROVOD_RENDEZVOUS_ADDR = "HOROVOD_GLOO_RENDEZVOUS_ADDR"
HOROVOD_RENDEZVOUS_PORT = "HOROVOD_GLOO_RENDEZVOUS_PORT"
HOROVOD_CONTROLLER = "HOROVOD_CONTROLLER"
HOROVOD_CPU_OPERATIONS = "HOROVOD_CPU_OPERATIONS"

# telemetry (docs/observability.md): per-worker Prometheus endpoint
# on METRICS_PORT (+ proc index in multi-process jobs) and the
# worker->coordinator snapshot push cadence feeding the job-wide
# /metrics on the launcher's rendezvous service
HOROVOD_METRICS_PORT = "HOROVOD_METRICS_PORT"
HOROVOD_METRICS_PUSH_SECONDS = "HOROVOD_METRICS_PUSH_SECONDS"

# job-wide tracing (docs/timeline.md "Job-wide traces"): the
# flight-recorder ring size (events; 0 disables), the directory stall
# auto-dumps and hvd.dump_trace() default into (unset = KV push only),
# and the clock-sync re-sample cadence mapping each worker's timeline
# epoch onto the launcher's clock (0 disables)
HOROVOD_TRACE_RING_EVENTS = "HOROVOD_TRACE_RING_EVENTS"
HOROVOD_TRACE_DUMP_DIR = "HOROVOD_TRACE_DUMP_DIR"
HOROVOD_TRACE_CLOCK_SYNC_SECONDS = "HOROVOD_TRACE_CLOCK_SYNC_SECONDS"

# chaos + liveness + fabric hardening (docs/fault_tolerance.md):
# HOROVOD_FAULT_PLAN names a seeded fault plan (inline JSON, @path, or
# a bare file path; horovodrun --fault-plan); HOROVOD_FAULT_SEED
# overrides the plan's seed.  Workers beat the coordinator every
# HEARTBEAT_INTERVAL seconds (0 disables); the coordinator declares a
# proc dead after HEARTBEAT_WINDOW seconds without a beat (0 = 1.5x
# the interval — detection inside 2x the interval).  Fabric retries
# are bounded by attempts AND a wall deadline.
HOROVOD_FAULT_PLAN = "HOROVOD_FAULT_PLAN"
HOROVOD_FAULT_SEED = "HOROVOD_FAULT_SEED"
HOROVOD_HEARTBEAT_INTERVAL_SECONDS = "HOROVOD_HEARTBEAT_INTERVAL_SECONDS"
HOROVOD_HEARTBEAT_WINDOW_SECONDS = "HOROVOD_HEARTBEAT_WINDOW_SECONDS"
HOROVOD_FABRIC_RETRY_ATTEMPTS = "HOROVOD_FABRIC_RETRY_ATTEMPTS"
HOROVOD_FABRIC_RETRY_DEADLINE_SECONDS = \
    "HOROVOD_FABRIC_RETRY_DEADLINE_SECONDS"

# coordinator crash survival + steady-state bypass
# (docs/fault_tolerance.md "Coordinator crash survival"):
# COORD_JOURNAL names the launcher-side control-plane journal a
# restarted rendezvous service replays (epoch-fenced);
# COORD_OUTAGE_DEADLINE bounds how long replay-safe fabric requests
# keep retrying across a coordinator outage; BYPASS_AFTER_CYCLES is
# the K identical negotiation cycles that arm the coordinator-free
# fast path (0 disables the bypass); BYPASS_WAIT_SECONDS bounds each
# armed cycle's wait for the cached tensors before it forces the
# unanimous fallback to full negotiation.
HOROVOD_COORD_JOURNAL = "HOROVOD_COORD_JOURNAL"
HOROVOD_COORD_OUTAGE_DEADLINE_SECONDS = \
    "HOROVOD_COORD_OUTAGE_DEADLINE_SECONDS"
HOROVOD_BYPASS_AFTER_CYCLES = "HOROVOD_BYPASS_AFTER_CYCLES"
HOROVOD_BYPASS_WAIT_SECONDS = "HOROVOD_BYPASS_WAIT_SECONDS"

# per-host aggregator tier (docs/fault_tolerance.md "Per-host
# aggregator tier"): TIER selects the control-plane topology (flat =
# every proc talks to the coordinator, host = one aggregator per host
# batches its workers' traffic upstream); LINGER_MS is the batching
# window the aggregator's flusher waits for co-reporting local
# workers; FALLBACK_DEADLINE bounds how long a worker's requests
# retry against a silent aggregator before falling back to direct
# coordinator mode (deliberately much tighter than the coordinator
# outage deadline — the fallback IS the recovery).
HOROVOD_CONTROL_PLANE_TIER = "HOROVOD_CONTROL_PLANE_TIER"
HOROVOD_AGG_LINGER_MS = "HOROVOD_AGG_LINGER_MS"
HOROVOD_AGG_FALLBACK_DEADLINE_SECONDS = \
    "HOROVOD_AGG_FALLBACK_DEADLINE_SECONDS"

# shared-secret for the launcher's HMAC-authenticated KV channel
# (reference runner/common/util/secret.py; hex in the env)
HOROVOD_SECRET_KEY = "HOROVOD_SECRET_KEY"
# elastic: crash-durable state spill directory (common/elastic.py)
# and the init-barrier wait for the first rendezvous (reference
# --elastic-timeout semantics, also a worker-side knob here)
HOROVOD_STATE_SPILL = "HOROVOD_STATE_SPILL"
HOROVOD_ELASTIC_TIMEOUT = "HOROVOD_ELASTIC_TIMEOUT"
# bound on the clean-teardown coordination barrier
# (jax.distributed.shutdown) during an elastic re-init: a peer wedged
# in a data-plane collective (e.g. an armed bypass vote racing the
# resize) can never reach the barrier — after this many seconds the
# worker abandons it and exec-restarts into the new round instead of
# deadlocking the whole job (docs/fault_tolerance.md)
HOROVOD_TEARDOWN_BARRIER_SECONDS = "HOROVOD_TEARDOWN_BARRIER_SECONDS"
# coordinator journal bounds (runner/http/journal.py): whole-file
# compaction threshold and the per-value KV journaling cap
HOROVOD_COORD_JOURNAL_MAX_BYTES = "HOROVOD_COORD_JOURNAL_MAX_BYTES"
HOROVOD_COORD_JOURNAL_KV_MAX_BYTES = \
    "HOROVOD_COORD_JOURNAL_KV_MAX_BYTES"

# TPU-native additions
# uniform wire shorthand: one format for every hop (a 16-bit value
# applies to both hops of a decomposed reduction; int8/int4 apply to
# the cross hop only — the inner hop stays full width)
HOROVOD_WIRE_DTYPE = "HOROVOD_WIRE_DTYPE"  # f32|fp16|bf16|int8|int4
# per-hop wire pair (docs/concepts.md "Per-hop wire"): INNER is the
# fast intra-host/ICI hop (f32 | fp16 | bf16 — quantized formats are
# never legal there), OUTER the slow cross-host/DCN hop (f32 | fp16 |
# bf16 | int8 | int4).  OUTER wins over the WIRE_DTYPE shorthand.
HOROVOD_WIRE_INNER = "HOROVOD_WIRE_INNER"
HOROVOD_WIRE_OUTER = "HOROVOD_WIRE_OUTER"
# flat | hierarchical | torus (generic spelling; the reference's
# HOROVOD_HIERARCHICAL_ALLREDUCE / HOROVOD_TORUS_ALLREDUCE booleans
# above are honored as aliases)
HOROVOD_ALLREDUCE_ALGORITHM = "HOROVOD_ALLREDUCE_ALGORITHM"
# reducescatter backward convention: default matches the reference
# (Sum grad x= size, Average unscaled); set to 1 for the true adjoint
# of the forward (docs/migration.md "reducescatter gradients")
HOROVOD_EXACT_ADJOINT_REDUCESCATTER = \
    "HOROVOD_EXACT_ADJOINT_REDUCESCATTER"
HOROVOD_TPU_PLATFORM = "HOROVOD_TPU_PLATFORM"  # jax platform for the mesh
HOROVOD_TPU_RANKS_PER_PROC = "HOROVOD_TPU_RANKS_PER_PROC"
HOROVOD_TPU_COORDINATOR = "HOROVOD_TPU_COORDINATOR"
HOROVOD_TPU_NUM_PROCS = "HOROVOD_TPU_NUM_PROCS"
HOROVOD_TPU_PROC_INDEX = "HOROVOD_TPU_PROC_INDEX"
# alltoall SPMD schedule (ops/xla_ops.py: auto | oneshot | diag)
HOROVOD_TPU_ALLTOALL_SCHEDULE = "HOROVOD_TPU_ALLTOALL_SCHEDULE"
# fusion pack goes multithreaded above this bucket size (csrc
# hvd_pack_mt); a third autotune dimension
HOROVOD_TPU_PACK_MT_THRESHOLD = "HOROVOD_TPU_PACK_MT_THRESHOLD"

# MPMD pipeline runtime (docs/parallelism.md "MPMD pipeline runtime";
# parallel/runtime.py + schedule.py): number of pipeline stages the
# job is carved into (1 = no pipelining), microbatches per step (0 =
# auto: 2·pp for every schedule), the schedule (gpipe |
# 1f1b | interleaved), and model chunks per stage for the interleaved
# schedule.  horovodrun --pipeline-stages / --num-microbatches /
# --pipeline-schedule hand these off; (schedule, n_micro) is also the
# autotuner's seventh dimension, latched per negotiation entry and
# cross-rank validated like the wire pair and algorithm.
HOROVOD_PP_STAGES = "HOROVOD_PP_STAGES"
HOROVOD_PP_MICROBATCHES = "HOROVOD_PP_MICROBATCHES"
HOROVOD_PP_SCHEDULE = "HOROVOD_PP_SCHEDULE"
HOROVOD_PP_CHUNKS = "HOROVOD_PP_CHUNKS"
# autotune warm-start cache (docs/autotune.md "Warm start"): a local
# JSON file of converged best configs keyed by (bucket signature,
# topology, world size); jobs reload yesterday's optimum at start
HOROVOD_AUTOTUNE_CACHE = "HOROVOD_AUTOTUNE_CACHE"

# ZeRO-grade weight-update sharding (docs/parallelism.md
# "Weight-update sharding"; core/sharded.py): SHARDED_OPTIMIZER=1
# makes DistributedOptimizer default to sharded=True on every
# frontend — gradients reducescatter, each rank updates its 1/dp
# shard of params + optimizer state, the updated params allgather
# back on the configured wire.  SHARD_LAYOUT picks the shard-bucket
# granularity (bucket | flat) and is the autotuner's EIGHTH
# dimension.
HOROVOD_SHARDED_OPTIMIZER = "HOROVOD_SHARDED_OPTIMIZER"
HOROVOD_SHARD_LAYOUT = "HOROVOD_SHARD_LAYOUT"

# bucket-granular comm/compute overlap on the compiled path
# (docs/concepts.md "Bucket-granular dispatch"; ops/compiled.py):
# OVERLAP_BUCKET_BYTES splits the compiled grouped reduction into
# per-bucket programs of at most this many payload bytes each,
# dispatched as each bucket's gradients arrive so the collectives
# pipeline against the remaining backward compute (0 = one grouped
# program, the pre-overlap behavior).  OVERLAP_AUTOTUNE sweeps the
# bucket size as the autotuner's NINTH dimension.  Reducers LATCH
# the value once per call/stream, so a mid-step flip can never split
# one step across two bucketings.
HOROVOD_OVERLAP_BUCKET_BYTES = "HOROVOD_OVERLAP_BUCKET_BYTES"
HOROVOD_OVERLAP_AUTOTUNE = "HOROVOD_OVERLAP_AUTOTUNE"

# end-to-end step integrity (docs/fault_tolerance.md "Silent data
# corruption"; core/integrity.py): INTEGRITY=0 disables the wire
# checksums + implicated-rank vote (they default ON — the digests are
# one xor-fold pass per buffer); SENTINEL_STEPS is the divergence
# sentinel's cadence (param-fingerprint MIN/MAX agreement every N
# steps, 0 = off); EVICT_AFTER escalates the N-th detection
# implicating one rank into a HostEvictionError so the driver's
# blacklist verdict evicts the host (0 = always roll back, never
# evict); MAX_GRAD_NORM arms the update guard's norm bound (0 = only
# the nonfinite check).
HOROVOD_INTEGRITY = "HOROVOD_INTEGRITY"
HOROVOD_INTEGRITY_SENTINEL_STEPS = "HOROVOD_INTEGRITY_SENTINEL_STEPS"
HOROVOD_INTEGRITY_EVICT_AFTER = "HOROVOD_INTEGRITY_EVICT_AFTER"
HOROVOD_INTEGRITY_MAX_GRAD_NORM = "HOROVOD_INTEGRITY_MAX_GRAD_NORM"

# expert parallelism (docs/parallelism.md "Expert parallelism";
# parallel/moe.py + ops/compiled.py CompiledAlltoall): MOE_EXPERTS is
# the total expert count (0 = no MoE layers, the default); the
# capacity factor sizes each expert's fixed token buffer
# (capacity = ceil(cf * tokens * topk / experts), deterministic
# drop/pad keeps compiled shapes static → zero steady-state
# recompiles); TOPK is the router fan-out.  MOE_EP caps the
# expert-parallel degree (0 = every rank; experts shard across the ep
# axis, tokens ride the fused quantized alltoall).  (ep × capacity
# factor) is the autotuner's TENTH dimension, swept only when
# MOE_EXPERTS > 0.
HOROVOD_MOE_EXPERTS = "HOROVOD_MOE_EXPERTS"
HOROVOD_MOE_CAPACITY_FACTOR = "HOROVOD_MOE_CAPACITY_FACTOR"
HOROVOD_MOE_TOPK = "HOROVOD_MOE_TOPK"
HOROVOD_MOE_EP = "HOROVOD_MOE_EP"

# multi-tenant fleet controller (docs/fleet.md; horovodrun
# --fleet-spec): the JSON fleet spec source (inline, @path, or bare
# path), the reconciliation cadence, the controller's own journal
# (crash-restartable: HOROVOD_FLEET_RESUME=1 replays it), the
# deterministic preemption/fault evidence log the day-in-the-life
# gate compares byte-for-byte, the controller's Prometheus port, and
# the placement debounce/cooldown windows (in reconcile ticks) that
# keep a resize storm from thrashing rounds.
HOROVOD_FLEET_SPEC = "HOROVOD_FLEET_SPEC"
HOROVOD_FLEET_RECONCILE_SECONDS = "HOROVOD_FLEET_RECONCILE_SECONDS"
HOROVOD_FLEET_JOURNAL = "HOROVOD_FLEET_JOURNAL"
HOROVOD_FLEET_RESUME = "HOROVOD_FLEET_RESUME"
HOROVOD_FLEET_EVIDENCE_LOG = "HOROVOD_FLEET_EVIDENCE_LOG"
HOROVOD_FLEET_METRICS_PORT = "HOROVOD_FLEET_METRICS_PORT"
HOROVOD_FLEET_SETTLE_TICKS = "HOROVOD_FLEET_SETTLE_TICKS"
HOROVOD_FLEET_BLACKLIST_TICKS = "HOROVOD_FLEET_BLACKLIST_TICKS"

# pod-scale data plane (docs/data.md): DATA_SHARD_JOURNAL names the
# shard ledger's cursor journal file (unset = in-memory only — no
# exactly-once guarantee across restarts); DATA_SHARD_SEED seeds the
# deterministic sample permutation the shard planner splits (same
# seed → byte-identical shard plans, the data drill's evidence);
# DATA_QUEUE_SIZE bounds each shard server's staged-batch queue (the
# backpressure window DATA_QUEUE_DEPTH exports); DATA_ACK_POLL_SECONDS
# is the ledger's cadence for draining consumer acks from the KV
# fabric into journaled cursors (the bounded cursor-lag window);
# DATA_ASYNC_CKPT=0 forces utils/checkpoint.py save_rank0-style
# inline saves instead of the background CRC-anchored streamer.
HOROVOD_DATA_SHARD_JOURNAL = "HOROVOD_DATA_SHARD_JOURNAL"
HOROVOD_DATA_SHARD_SEED = "HOROVOD_DATA_SHARD_SEED"
HOROVOD_DATA_QUEUE_SIZE = "HOROVOD_DATA_QUEUE_SIZE"
HOROVOD_DATA_ACK_POLL_SECONDS = "HOROVOD_DATA_ACK_POLL_SECONDS"
HOROVOD_DATA_ASYNC_CKPT = "HOROVOD_DATA_ASYNC_CKPT"

#: Launcher↔worker handoff ABI: env vars the launcher exports for its
#: own workers and users never set by hand.  hvdlint checker 5
#: (`knob-undocumented`) exempts these from the docs/migration.md
#: knob-table requirement; everything else read anywhere in the tree
#: must be documented.  Keep this list honest — moving a knob here to
#: silence the checker defeats the registry.
INTERNAL_KNOBS = (
    # rank/topology handoff (reference gloo_run.py:66-103)
    "HOROVOD_RANK", "HOROVOD_SIZE", "HOROVOD_LOCAL_RANK",
    "HOROVOD_LOCAL_SIZE", "HOROVOD_CROSS_RANK", "HOROVOD_CROSS_SIZE",
    "HOROVOD_HOSTNAME", "HOROVOD_CONTROLLER", "HOROVOD_CPU_OPERATIONS",
    # multi-process mesh handoff (proc_run -> workers)
    "HOROVOD_TPU_PROC_INDEX", "HOROVOD_TPU_NUM_PROCS",
    "HOROVOD_TPU_COORDINATOR", "HOROVOD_TPU_RANKS_PER_PROC",
    "HOROVOD_TPU_RANKS_OF_PROC", "HOROVOD_TPU_HOST_OF_RANK",
    "HOROVOD_TPU_INIT_TIMEOUT",
    # spark driver -> task handoff (spark/task/)
    "HOROVOD_SPARK_PYTHONPATH", "HOROVOD_SPARK_WORK_DIR",
)

DEFAULT_FUSION_THRESHOLD_BYTES = 64 * 1024 * 1024
#: Overlap bucket-size grid the autotuner sweeps (ninth dimension)
#: and docs/autotune.md documents: 0 = grouped single program, then
#: 1/4/16/64 MiB bucket ceilings.  Lives here (not core/autotune.py)
#: so ops/compiled.py and the benches import it without pulling the
#: tuner.
OVERLAP_BUCKET_CHOICES = (0, 1 << 20, 4 << 20, 16 << 20, 64 << 20)
DEFAULT_CYCLE_TIME_MS = 1.0
DEFAULT_CACHE_CAPACITY = 1024
DEFAULT_STALL_WARNING_SECS = 60.0


def get_bool(name, default=False):
    val = os.environ.get(name)
    if val is None:
        return default
    return val.strip().lower() in ("1", "true", "yes", "on")


def _warn_malformed(name, val, default):
    # loud, not fatal: an operator's typo (e.g. FOO=64M) must not be
    # silently replaced by the default with nothing in the logs
    logging.getLogger("horovod_tpu").warning(
        "%s=%r is not a valid number; using default %r",
        name, val, default)


def get_int(name, default=0):
    val = os.environ.get(name)
    if val is None or not val.strip():
        return default
    try:
        return int(val)
    except ValueError:
        _warn_malformed(name, val, default)
        return default


def get_float(name, default=0.0):
    val = os.environ.get(name)
    if val is None or not val.strip():
        return default
    try:
        return float(val)
    except ValueError:
        _warn_malformed(name, val, default)
        return default


def get_str(name, default=None):
    return os.environ.get(name, default)


def require_str(name):
    """A handoff variable that MUST be present: missing-or-empty
    raises naming the variable, instead of leaking None into an
    address/port where it fails as an opaque downstream error."""
    val = os.environ.get(name)
    if val is None or not val.strip():
        raise KeyError(
            f"{name} missing from the environment — the launcher "
            f"handoff did not reach this process")
    return val


def require_int(name):
    return int(require_str(name))


# -- worker-side logging (reference common/logging.cc + env_parser.cc
#    SetLogLevelFromEnv/SetBoolFromEnv(HOROVOD_LOG_HIDE_TIME)) --------------

_LOG_LEVELS = {
    "trace": logging.DEBUG,     # python logging has no TRACE tier
    "debug": logging.DEBUG,
    "info": logging.INFO,
    "warning": logging.WARNING,
    "error": logging.ERROR,
    "fatal": logging.CRITICAL,
}


def setup_logging():
    """Configure the ``horovod_tpu`` logger from ``HOROVOD_LOG_LEVEL``
    and ``HOROVOD_LOG_HIDE_TIME``.

    The runner exports both (runner/config_parser.py) exactly like the
    reference launcher, and the reference workers honor them in
    ``logging.cc``; called from ``hvd.init()`` so launched workers do
    too.  Without an explicit level the logger is left alone (library
    default: warnings propagate to whatever the host app configured)."""
    level = get_str(HOROVOD_LOG_LEVEL)
    hide_time = get_bool(HOROVOD_LOG_HIDE_TIME)
    logger = logging.getLogger("horovod_tpu")
    if level is None:
        return logger
    logger.setLevel(_LOG_LEVELS.get(level.strip().lower(),
                                    logging.WARNING))
    fmt = "[%(levelname)s] %(message)s" if hide_time else \
        "[%(asctime)s.%(msecs)03d, %(levelname)s] %(message)s"
    handler = None
    for h in logger.handlers:
        if getattr(h, "_hvd_env_handler", False):
            handler = h
            break
    if handler is None:
        handler = logging.StreamHandler()
        handler._hvd_env_handler = True
        logger.addHandler(handler)
        # this logger now owns its output (reference logging.cc writes
        # its own stream); propagating too would double every record
        # through the host application's root handlers
        logger.propagate = False
    handler.setFormatter(logging.Formatter(fmt, datefmt="%H:%M:%S"))
    return logger


class Config:
    """Runtime knobs resolved from the environment at init() time.

    Mirrors the parse performed in the reference's BackgroundThreadLoop
    (operations.cc:459-650): fusion threshold, cycle time, cache
    capacity, stall-inspector and autotune settings.
    """

    def __init__(self):
        self.fusion_threshold_bytes = get_int(
            HOROVOD_FUSION_THRESHOLD, DEFAULT_FUSION_THRESHOLD_BYTES)
        self.cycle_time_ms = get_float(HOROVOD_CYCLE_TIME, DEFAULT_CYCLE_TIME_MS)
        # fusion pack goes multithreaded above this bucket size
        # (csrc hvd_pack_mt); a third autotune dimension
        self.pack_mt_threshold_bytes = get_int(
            HOROVOD_TPU_PACK_MT_THRESHOLD, 8 << 20)
        self.cache_capacity = get_int(HOROVOD_CACHE_CAPACITY, DEFAULT_CACHE_CAPACITY)
        # default wire formats for float allreduce/reducescatter
        # payloads (per-request wire_dtype=/wire_inner= override;
        # autotune sweeps the per-hop PAIR as one categorical).
        # wire_dtype is the OUTER (cross-host/DCN) hop — or the only
        # hop of a flat collective; wire_inner the intra-host/ICI hop.
        # HOROVOD_WIRE_DTYPE stays as the uniform shorthand (the
        # engine expands a 16-bit value onto both hops); an explicit
        # HOROVOD_WIRE_OUTER wins over it.  None = full width.
        from ..ops.quantize import (normalize_inner_wire,
                                    normalize_wire_dtype)
        self.wire_dtype = normalize_wire_dtype(
            get_str(HOROVOD_WIRE_OUTER) or get_str(HOROVOD_WIRE_DTYPE))
        self.wire_inner = normalize_inner_wire(
            get_str(HOROVOD_WIRE_INNER))
        # default reduction algorithm for float Sum/Average allreduces
        # (per-request algorithm= overrides; autotune sweeps this as
        # its sixth dimension).  The reference's boolean toggles
        # (HOROVOD_TORUS_ALLREDUCE wins over HIERARCHICAL, matching
        # the fork's NCCL dispatch order) alias the generic knob.
        from .topology import normalize_algorithm
        if get_bool(HOROVOD_TORUS_ALLREDUCE):
            self.algorithm = "torus"
        elif get_bool(HOROVOD_HIERARCHICAL_ALLREDUCE):
            self.algorithm = "hierarchical"
        else:
            self.algorithm = normalize_algorithm(
                get_str(HOROVOD_ALLREDUCE_ALGORITHM))
        self.timeline_filename = get_str(HOROVOD_TIMELINE)
        if self.timeline_filename == "DYNAMIC":
            # reference sentinel (test_torch.py:54): timeline support
            # enabled but no file until start_timeline() names one
            self.timeline_filename = None
        self.timeline_mark_cycles = get_bool(HOROVOD_TIMELINE_MARK_CYCLES)
        self.autotune = get_bool(HOROVOD_AUTOTUNE)
        self.autotune_log = get_str(HOROVOD_AUTOTUNE_LOG)
        self.autotune_warmup_samples = get_int(HOROVOD_AUTOTUNE_WARMUP_SAMPLES, 3)
        self.autotune_steps_per_sample = get_int(HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE, 10)
        self.autotune_max_samples = get_int(
            HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES, 20)
        self.stall_check_disable = get_bool(HOROVOD_STALL_CHECK_DISABLE)
        self.stall_warning_secs = get_float(
            HOROVOD_STALL_CHECK_TIME_SECONDS, DEFAULT_STALL_WARNING_SECS)
        self.stall_shutdown_secs = get_float(HOROVOD_STALL_SHUTDOWN_TIME_SECONDS, 0.0)
        self.elastic = get_bool(HOROVOD_ELASTIC)
        # telemetry exposition (docs/observability.md): metrics_port 0
        # = no per-worker HTTP endpoint.  The snapshot push that feeds
        # the coordinator's job-wide /metrics defaults on (cheap: one
        # small KV put per interval) whenever an endpoint is enabled,
        # and can be forced on/off explicitly.
        self.metrics_port = get_int(HOROVOD_METRICS_PORT, 0)
        self.metrics_push_secs = get_float(
            HOROVOD_METRICS_PUSH_SECONDS,
            2.0 if self.metrics_port else 0.0)
        # flight recorder (docs/timeline.md): always-on bounded ring of
        # recent timeline events, default on — the emit path is a dict
        # + deque append, cheap enough for the dispatch loop; 0
        # disables.  Stall warnings auto-dump it (engine.dump_trace).
        self.trace_ring_events = get_int(HOROVOD_TRACE_RING_EVENTS, 4096)
        self.trace_dump_dir = get_str(HOROVOD_TRACE_DUMP_DIR)
        # NTP-style clock sync against the launcher's clock, re-sampled
        # for drift; multi-process only (single-process traces carry a
        # wall-clock mapping from birth)
        self.clock_sync_secs = get_float(
            HOROVOD_TRACE_CLOCK_SYNC_SECONDS, 30.0)
        # process-set removal is a barrier across local rank threads;
        # this bounds the wait for peers' votes and the drain of
        # in-flight collectives on the set
        self.ps_removal_timeout_secs = get_float(
            HOROVOD_PROCESS_SET_REMOVAL_TIMEOUT, 60.0)
        # worker liveness (docs/fault_tolerance.md): heartbeat cadence
        # to the coordinator in multi-process jobs; 0 disables.  The
        # coordinator's death window rides autotune_kwargs from the
        # same env so both sides agree.
        self.heartbeat_secs = get_float(
            HOROVOD_HEARTBEAT_INTERVAL_SECONDS, 5.0)
        # steady-state negotiation bypass (docs/fault_tolerance.md +
        # core/bypass.py): after K identical negotiation cycles the
        # ranks agree via a bitvector exchange and skip the
        # coordinator; 0 disables.  The wait bound forces the
        # unanimous fallback when a cached tensor never goes ready.
        self.bypass_after_cycles = get_int(
            HOROVOD_BYPASS_AFTER_CYCLES, 5)
        self.bypass_wait_secs = get_float(
            HOROVOD_BYPASS_WAIT_SECONDS, 10.0)
        # chaos fault plan (raw source; parsed by chaos.plan_from_env
        # at init so a malformed plan fails loudly, not silently)
        self.fault_plan = get_str(HOROVOD_FAULT_PLAN)
        # MPMD pipeline runtime (parallel/runtime.py): stage count,
        # schedule and microbatch count.  (pp_schedule, pp_n_micro)
        # is ONE autotune categorical (the seventh dimension) — the
        # runtime latches the pair at each step start, and the engine
        # latches it per negotiation entry on the step's gradient
        # reduces so a mid-step autotune flip can never split one
        # step across two schedules.
        self.pp_stages = get_int(HOROVOD_PP_STAGES, 1)
        raw_sched = get_str(HOROVOD_PP_SCHEDULE)
        if raw_sched:
            # lazy: importing parallel.schedule executes the whole
            # parallel package (flax models, attention helpers) —
            # only jobs that actually set a schedule pay that, and
            # they import it again at make_lm_train_step anyway
            from ..parallel.schedule import normalize_schedule
            self.pp_schedule = normalize_schedule(raw_sched) or "1f1b"
        else:
            self.pp_schedule = "1f1b"
        self.pp_n_micro = get_int(HOROVOD_PP_MICROBATCHES, 0)
        self.pp_chunks = get_int(HOROVOD_PP_CHUNKS, 0)
        # autotune warm-start cache file (core/autotune.py load/save)
        self.autotune_cache = get_str(HOROVOD_AUTOTUNE_CACHE)
        # ZeRO-grade weight-update sharding (core/sharded.py): the
        # process-wide default frontends resolve sharded=None against,
        # and the shard-bucket layout — the autotuner's EIGHTH
        # dimension, re-read by the updaters at each (re)build so a
        # sweep flip re-shards deterministically instead of mid-step
        self.sharded_optimizer = get_bool(HOROVOD_SHARDED_OPTIMIZER)
        raw_layout = get_str(HOROVOD_SHARD_LAYOUT)
        if raw_layout:
            # lazy normalize: core.sharded is tiny, but a malformed
            # value must fail loudly at init, not at first step
            from ..core.sharded import normalize_shard_layout
            self.shard_layout = normalize_shard_layout(raw_layout)
        else:
            self.shard_layout = "bucket"
        # bucket-granular comm/compute overlap (ops/compiled.py):
        # max payload bytes per compiled bucket program (0 = one
        # grouped program), and whether the autotuner sweeps the
        # bucket size as its ninth dimension.  The reducer latches
        # the value once per call/stream — a mid-step autotune flip
        # never splits one step across bucketings.
        self.overlap_bucket_bytes = get_int(
            HOROVOD_OVERLAP_BUCKET_BYTES, 0)
        self.overlap_autotune = get_bool(HOROVOD_OVERLAP_AUTOTUNE)
        # end-to-end step integrity (core/integrity.py): wire
        # checksums + the implicated-rank vote default ON; the
        # sentinel cadence and guards are read by StepSentinel, the
        # eviction threshold by the engine's scoreboard
        self.integrity = get_bool(HOROVOD_INTEGRITY, True)
        self.integrity_sentinel_steps = get_int(
            HOROVOD_INTEGRITY_SENTINEL_STEPS, 50)
        self.integrity_evict_after = get_int(
            HOROVOD_INTEGRITY_EVICT_AFTER, 3)
        self.integrity_max_grad_norm = get_float(
            HOROVOD_INTEGRITY_MAX_GRAD_NORM, 0.0)
        # expert parallelism (parallel/moe.py): total experts (0 = no
        # MoE), fixed-capacity routing factor, router top-k, and the
        # expert-parallel degree cap (0 = every rank).  (ep ×
        # capacity factor) is the autotuner's TENTH dimension, swept
        # only when experts are present; layers re-read the pair at
        # each step start so a sweep flip re-routes deterministically
        # between steps, never inside one.
        self.moe_experts = get_int(HOROVOD_MOE_EXPERTS, 0)
        self.moe_capacity_factor = get_float(
            HOROVOD_MOE_CAPACITY_FACTOR, 1.25)
        self.moe_topk = get_int(HOROVOD_MOE_TOPK, 2)
        self.moe_ep = get_int(HOROVOD_MOE_EP, 0)
