"""MPMD pipeline runtime: process-set-backed stage meshes, explicit
1F1B / interleaved schedules, bubble-overlapped gradient collectives.

pipeline.py's GPipe compiles the whole pipeline into one fused scan —
elegant, but the schedule is frozen into the program: backward cannot
start before the last forward (no 1F1B), nothing can overlap the
bubbles, and every stage lives inside one SPMD program on one mesh.
This module is the MPMD formulation (arXiv:2412.14374): the job is
carved into per-stage meshes backed by process sets, each stage runs
an explicit instruction stream (schedule.py) against its own compiled
chunk programs, and the dp-dimension gradient allreduces are routed
through the engine's ASYNC submit at the schedule's ``reduce`` ticks —
so the wire time of the gradient exchange hides inside the pipeline
bubbles instead of serializing after the step (the per-hop quantized
wire and reduction algorithm of the engine path apply to these
collectives unchanged).

Two substrates share the schedule executor and the chunk programs:

* :class:`LocalPipelineRuntime` — one process, stage meshes are
  device sub-grids of a ``dp×tp×pp`` mesh; dp/tp/sp collectives
  compile into the per-stage programs (XLA inserts them from the
  shardings) and stage hops are ``device_put``s.  This is the
  ``make_lm_train_step(..., pipeline=...)`` path and what the
  benchmarks drive.
* :class:`MpmdWorker` — one instance per engine rank (SPMD style:
  every rank runs the same code, its rank selects its stage and
  stream).  Activation / gradient hops ride ``hvd.broadcast`` on
  adjacent-pair process sets; dp gradient reduces ride
  ``hvd.grouped_allreduce_async`` on the per-stage sets, submitted at
  ``reduce`` ticks and synchronized only before the optimizer update.
  Tensor parallelism stays inside each worker's local devices (a TPU
  host drives its chips from one process), so dp×tp×pp jobs run with
  tp as a proc-local mesh axis.

The latched ``(schedule, n_micro)`` pair is the autotuner's seventh
dimension: re-read from the engine config at every step START (never
mid-step), snapped to the nearest legal microbatch count, stamped on
every overlapped gradient reduce (``Request.pp_sched``) and
cross-rank validated by the engine and coordinator exactly like the
wire pair and reduction algorithm.

Chunk programs register through ops.compiled's ``_shared_program``
cache, so ``horovod_program_cache_{hits,misses}_total`` and
``horovod_compile_seconds_total`` cover the pipeline too — "zero
steady-state recompiles" is assertable from a scrape (tools/
pp_smoke.py does).  Per-stage timeline lanes (``pp.stage<k>``) carry
PP_FWD / PP_BWD / PP_BUBBLE spans so the merged ``GET /timeline``
attributes bubble time by stage.
"""

import logging
from dataclasses import dataclass, replace

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..common.topology import carve_stage_ranks
from .executor import (
    EngineTransport, LMStageExecutor, LocalTransport, StageState,
)
from .mesh import AXIS_ORDER, BATCH_AXES
from .schedule import (
    build_schedule, normalize_schedule, pp_label,
)

logger = logging.getLogger("horovod_tpu")

__all__ = [
    "PipelineSpec", "LocalPipelineRuntime", "MpmdWorker",
    "make_mpmd_lm_train_step", "stage_meshes_from",
]


@dataclass(frozen=True)
class PipelineSpec:
    """A dp×tp×pp decomposition request.

    ``pp`` stages × ``dp`` data shards × ``tp`` tensor shards;
    ``n_micro`` microbatches per step (0 = auto: ``2*pp``, the
    smallest count that keeps a 1F1B pipeline reasonably full);
    ``chunks`` model chunks per stage (0 = auto: 2 for interleaved,
    1 otherwise).  ``schedule`` ∈ gpipe | 1f1b | interleaved."""
    pp: int
    dp: int = 1
    tp: int = 1
    n_micro: int = 0
    schedule: str = "1f1b"
    chunks: int = 0

    def resolved(self):
        sched = normalize_schedule(self.schedule) or "1f1b"
        chunks = self.chunks or (2 if sched == "interleaved" else 1)
        n_micro = self.n_micro or max(2 * self.pp, 2)
        if sched == "interleaved" and n_micro % self.pp:
            n_micro = -(-n_micro // self.pp) * self.pp
        return replace(self, schedule=sched, chunks=chunks,
                       n_micro=n_micro)

    @classmethod
    def from_env(cls, config, dp=1, tp=1):
        """Build from the HOROVOD_PP_* knobs (common/env.py Config)."""
        return cls(pp=max(int(config.pp_stages), 1), dp=dp, tp=tp,
                   n_micro=int(getattr(config, "pp_n_micro", 0)),
                   schedule=getattr(config, "pp_schedule", "1f1b"),
                   chunks=int(getattr(config, "pp_chunks", 0)))


def snap_n_micro(n_micro, batch, n_stages, schedule):
    """Largest legal microbatch count <= the requested one: must
    divide the (per-dp-rank) batch, and divide by ``n_stages`` for
    the interleaved schedule.  Deterministic — every rank snaps the
    same way, so an autotune proposal that doesn't divide the batch
    degrades identically everywhere instead of desyncing the step."""
    n_micro = max(int(n_micro), 1)
    step = n_stages if schedule == "interleaved" else 1
    for m in range(min(n_micro, batch), 0, -1):
        if batch % m == 0 and m % step == 0:
            return m
    return 1


def stage_meshes_from(mesh):
    """Carve a ``pp``-axis mesh into per-stage sub-meshes (axes =
    AXIS_ORDER minus pp, same device order).  The pp axis sits where
    mesh.py put it — outside tp/sp, inside dp/fsdp — so each stage's
    sub-grid is contiguous in device order and its tp/sp collectives
    keep their ICI adjacency."""
    from jax.sharding import Mesh

    pp_idx = AXIS_ORDER.index("pp")
    n_stages = mesh.devices.shape[pp_idx]
    axes = tuple(a for a in AXIS_ORDER if a != "pp")
    out = []
    for s in range(n_stages):
        arr = np.take(mesh.devices, s, axis=pp_idx)
        out.append(Mesh(arr, axes))
    return out


# ---------------------------------------------------------------------------
# chunked TransformerLM stage programs


def _cfg_sig(cfg):
    """Stable per-process identity of a TransformerConfig for the
    shared program cache."""
    return repr(cfg)


def _chunk_param_shardings(mesh, chunk_params):
    """Megatron-rule shardings for one chunk's ``layers`` subtree on a
    stage mesh: the full-model rules minus the pp axis (the chunk's
    leading layer axis is stage-local, not sharded)."""
    from .sharding import transformer_param_spec

    def spec(path, leaf):
        full = transformer_param_spec(path, leaf)
        parts = tuple(full)
        if parts[:1] == ("pp",):
            parts = (None,) + parts[1:]
        return NamedSharding(mesh, P(*parts))

    # synthesize the full-model path prefix so the layer rules match
    prefix = (jax.tree_util.DictKey("layers"),)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec(prefix + path, leaf), chunk_params)


class LMStagePrograms:
    """The chunked TransformerLM compute vocabulary, one builder per
    (cfg, chunk layout): forward and backward programs for first /
    mid / last / single chunks, each jitted once per operand signature
    through ops.compiled's ``_shared_program`` cache.

    Backward programs re-run the chunk forward inside ``jax.vjp``
    (recompute-style 1F1B): per in-flight microbatch a stage stores
    only the chunk INPUT, the memory shape that makes 1F1B's
    O(stages) activation bound real.  The last chunk's forward tick
    only records its input — loss and gradients come out of ONE
    value_and_grad program at the backward tick, so the loss head is
    never computed twice."""

    def __init__(self, cfg, total_chunks, attention_fn=None):
        from ..models.transformer import (
            DecoderBlock, RMSNorm, rope_angles)
        from jax import lax

        if cfg.n_layers % total_chunks != 0:
            raise ValueError(
                f"n_layers={cfg.n_layers} not divisible into "
                f"{total_chunks} pipeline chunks (stages × chunks)")
        self.cfg = cfg
        self.total_chunks = total_chunks
        self.layers_per_chunk = cfg.n_layers // total_chunks
        self._sig = (_cfg_sig(cfg), total_chunks,
                     getattr(attention_fn, "__name__", None)
                     if attention_fn is not None else None)
        block = DecoderBlock(cfg, attention_fn) \
            if attention_fn is not None else DecoderBlock(cfg)
        angles = jnp.asarray(rope_angles(
            cfg.head_dim, cfg.max_seq_len, cfg.rope_theta))

        def chunk_body(lc, x):
            ang = angles[: x.shape[1]]

            def body(h, lp):
                h, _ = block.apply({"params": lp}, h, ang)
                return h, None
            x, _ = lax.scan(body, x, lc)
            return x

        def embed_in(emb, tokens):
            return emb[tokens].astype(cfg.dtype)

        def loss_out(emb, lnf, x, tokens):
            x = RMSNorm(cfg.dtype, name="ln_final").apply(
                {"params": lnf}, x)
            logits = jnp.einsum(
                "bsm,vm->bsv", x, emb.astype(cfg.dtype),
                preferred_element_type=jnp.float32)
            # lm_loss(logits[:, :-1], tokens[:, 1:]) with the target
            # gathered BEFORE the last position is dropped: under a
            # stage mesh with an sp axis the sequence arrives tiled
            # over sp, S - 1 positions cannot be tiled evenly, and
            # XLA's SPMD partitioner aborts the process on the
            # gather's transpose (a scatter) over an uneven tiling
            logp = jax.nn.log_softmax(logits)
            ll = jnp.take_along_axis(
                logp, jnp.roll(tokens, -1, axis=1)[..., None],
                axis=-1)[..., 0]
            return -jnp.mean(ll[:, :-1])

        # forward fns -----------------------------------------------------
        def fwd_first(emb, lc, tokens):
            return chunk_body(lc, embed_in(emb, tokens))

        def fwd_mid(lc, x):
            return chunk_body(lc, x)

        def last_loss(emb, lnf, lc, x, tokens):
            return loss_out(emb, lnf, chunk_body(lc, x), tokens)

        def single_loss(emb, lnf, lc, tokens):
            return loss_out(emb, lnf,
                            chunk_body(lc, embed_in(emb, tokens)),
                            tokens)

        # backward fns (recompute the forward inside the vjp) -------------
        def bwd_first(emb, lc, tokens, dy):
            _, vjp = jax.vjp(lambda e, l: fwd_first(e, l, tokens),
                             emb, lc)
            return vjp(dy)                       # (demb, dlc)

        def bwd_mid(lc, x, dy):
            _, vjp = jax.vjp(fwd_mid, lc, x)
            return vjp(dy)                       # (dlc, dx)

        def bwd_last(emb, lnf, lc, x, tokens):
            return jax.value_and_grad(
                last_loss, argnums=(0, 1, 2, 3))(emb, lnf, lc, x,
                                                 tokens)

        def bwd_single(emb, lnf, lc, tokens):
            return jax.value_and_grad(
                single_loss, argnums=(0, 1, 2))(emb, lnf, lc, tokens)

        self._fns = {"fwd_first": fwd_first, "fwd_mid": fwd_mid,
                     "bwd_first": bwd_first, "bwd_mid": bwd_mid,
                     "bwd_last": bwd_last, "bwd_single": bwd_single}

    def chunk_slice(self, layers, chunk):
        """Chunk ``chunk``'s slice of the stacked ``layers`` subtree
        (leading axis = n_layers, depth order = chunk order)."""
        per = self.layers_per_chunk
        lo = chunk * per
        return jax.tree_util.tree_map(lambda a: a[lo:lo + per], layers)

    def program(self, role, operands):
        """The jitted program for ``role``, shared per operand
        signature through the compiled-program cache (cache hits/
        misses/compile-seconds telemetry included) — mid chunks of
        every stage share ONE entry, and steady state is all hits."""
        from ..ops.compiled import _shared_program

        sig = tuple((tuple(a.shape), str(a.dtype))
                    for a in jax.tree_util.tree_leaves(operands))
        key = ("pp_prog", role, self._sig,
                jax.tree_util.tree_structure(operands), sig)
        fn = self._fns[role]
        return _shared_program(key, lambda: jax.jit(fn))


# ---------------------------------------------------------------------------
# shared schedule executor: parallel/executor.py (ScheduleExecutor /
# LMStageExecutor / StageState) — both runtimes below and the serving
# tier's continuous-batching inference pipeline dispatch through it

#: back-compat alias (the per-stage step state moved to executor.py)
_StageState = StageState


def _tree_div(tree, denom):
    return jax.tree_util.tree_map(lambda a: a / denom, tree)


def _pp_metrics(tag, bubble):
    from .. import telemetry

    reg = telemetry.registry()
    reg.counter(telemetry.PP_STEPS_FAMILY, telemetry.PP_STEPS_HELP,
                labelnames=telemetry.PP_STEPS_LABELS
                ).labels(schedule=tag).inc()
    reg.gauge(telemetry.PP_BUBBLE_FRACTION_FAMILY,
              telemetry.PP_BUBBLE_FRACTION_HELP).set(bubble)


# ---------------------------------------------------------------------------
# local (single-process) runtime


class LocalPipelineRuntime:
    """dp×tp×pp over one process's devices: stage meshes are sub-grids
    of a pp-axis mesh, stage hops are device_puts, dp/tp collectives
    compile into the chunk programs from the operand shardings.

    Exposes the ``(init, step, jit_step, tok_sharding)`` contract via
    :func:`make_mpmd_lm_train_step`."""

    def __init__(self, mesh, cfg, spec, optimizer, *,
                 attention_fn_factory=None):
        spec = spec.resolved()
        pp_idx = AXIS_ORDER.index("pp")
        mesh_pp = mesh.devices.shape[pp_idx]
        if mesh_pp != spec.pp:
            raise ValueError(
                f"mesh pp axis has {mesh_pp} stages but the spec asks "
                f"for {spec.pp}")
        if cfg.n_layers % spec.pp:
            # chunks can degrade at step time (autotune proposals),
            # pp itself cannot — fail at build, not the first step
            raise ValueError(
                f"n_layers={cfg.n_layers} not divisible into "
                f"{spec.pp} pipeline stages")
        self.mesh = mesh
        self.cfg = cfg
        self.spec = spec
        self.optimizer = optimizer
        self.stage_meshes = stage_meshes_from(mesh)
        self._att_factory = attention_fn_factory
        self._programs = {}   # n_chunks -> LMStagePrograms per stage
        self._schedules = {}
        self._shardings = {}  # (n_chunks, chunk) -> NamedSharding tree

    def _programs_for(self, total_chunks, stage):
        key = (total_chunks, stage if self._att_factory else -1)
        progs = self._programs.get(key)
        if progs is None:
            att = self._att_factory(self.stage_meshes[stage]) \
                if self._att_factory else None
            progs = LMStagePrograms(self.cfg, total_chunks,
                                    attention_fn=att)
            self._programs[key] = progs
        return progs

    def _latch(self, batch):
        """(schedule, n_micro, Schedule) for THIS step: the spec is
        the default, the engine config (autotune's seventh dimension)
        overrides when a live engine carries pp knobs, and n_micro
        snaps to the batch."""
        sched, m = self.spec.schedule, self.spec.n_micro
        chunks = self.spec.chunks
        cfg = _live_engine_config()
        if cfg is not None and getattr(cfg, "pp_stages", 1) > 1:
            sched = normalize_schedule(
                getattr(cfg, "pp_schedule", None)) or sched
            m = int(getattr(cfg, "pp_n_micro", 0)) or m
            if sched == "interleaved" and chunks < 2:
                chunks = 2
        if sched != "interleaved":
            chunks = 1
        if self.cfg.n_layers % (self.spec.pp * chunks):
            # an autotune proposal the model cannot chunk for —
            # degrade to 1f1b rather than failing the step
            sched, chunks = "1f1b", 1
        m = snap_n_micro(m, batch, self.spec.pp, sched)
        if sched == "interleaved" and (m < self.spec.pp
                                       or m % self.spec.pp):
            # no legal interleaved microbatching for this batch
            sched, chunks = "1f1b", 1
            m = snap_n_micro(m, batch, self.spec.pp, sched)
        key = (sched, m, chunks)
        if key not in self._schedules:
            self._schedules[key] = build_schedule(
                sched, self.spec.pp, m, chunks)
        return sched, m, chunks, self._schedules[key]

    def init(self, rng, sample_tokens):
        """Same init as make_lm_train_step: the dense twin, so params
        are bit-identical across the dense / GPipe / MPMD paths."""
        from ..models.transformer import TransformerLM

        params = TransformerLM(self.cfg).init(
            rng, sample_tokens)["params"]
        opt_state = self.optimizer.init(params)
        return {"params": params, "opt_state": opt_state,
                "step": jnp.zeros((), jnp.int32)}

    def tok_sharding(self):
        return NamedSharding(self.stage_meshes[0], P(BATCH_AXES, None))

    def _place_chunk(self, progs, layers, v, stage):
        lc = progs.chunk_slice(layers, v)
        # the sharding tree is a pure function of (stage mesh, chunk
        # layout), both fixed at construction — rebuilding it per
        # step puts host-side tree_map work inside the timed loop
        key = (progs.total_chunks, v)
        shd = self._shardings.get(key)
        if shd is None:
            shd = _chunk_param_shardings(self.stage_meshes[stage], lc)
            self._shardings[key] = shd
        return jax.device_put(lc, shd)

    def step(self, state, tokens):
        """One pipelined training step; returns (state', loss)."""
        B = int(tokens.shape[0])
        # each microbatch is sharded over the stage mesh's batch axes,
        # so n_micro snaps against the PER-DP-SHARD batch: B/M must
        # stay divisible by the dp width
        dpw = int(np.prod([self.stage_meshes[0].shape[a]
                           for a in BATCH_AXES]))
        sched, M, chunks, sobj = self._latch(
            B // dpw if dpw > 1 and B % dpw == 0 else B)
        tag = pp_label(sched, M)
        S = self.spec.pp
        C = sobj.total_chunks
        params = state["params"]
        mb_tokens = tokens.reshape((M, B // M) + tuple(tokens.shape[1:]))

        first_mesh, last_mesh = (self.stage_meshes[0],
                                 self.stage_meshes[-1])
        rep_first = NamedSharding(first_mesh, P())
        rep_last = NamedSharding(last_mesh, P())
        emb0 = jax.device_put(params["embed"], rep_first)
        embL = emb0 if S == 1 else jax.device_put(params["embed"],
                                                  rep_last)
        lnf = jax.device_put(params["ln_final"], rep_last)
        progs_by_stage = [self._programs_for(C, s) for s in range(S)]
        lc = [self._place_chunk(progs_by_stage[v % S],
                                params["layers"], v, v % S)
              for v in range(C)]

        st = [_StageState() for _ in range(S)]
        inbox = {}    # (v, mb) -> activation arriving at chunk v
        gbox = {}     # (v, mb) -> dL/d(output of chunk v)
        eng = _live_engine()
        tl = eng.timeline if eng is not None else None

        def mb_tok(s, mb):
            mesh = self.stage_meshes[s]
            return jax.device_put(
                mb_tokens[mb], NamedSharding(mesh, P(BATCH_AXES, None)))

        def span(s, op):
            if tl is None:
                import contextlib
                return contextlib.nullcontext()
            return tl.span(f"pp.stage{s}", op)

        # one executor per stage, all sharing one transport and one
        # inbox/gbox pair (the stage hop deposits locally); the
        # dispatch chain itself lives in parallel/executor.py
        transport = LocalTransport(self.stage_meshes)
        execs = [LMStageExecutor(
            progs=progs_by_stage[s],
            emb_first=emb0, emb_last=embL, lnf=lnf, layers=lc,
            mb_tok=(lambda mb, s=s: mb_tok(s, mb)),
            stage=s, n_stages=S, total_chunks=C,
            transport=transport,
            span=(lambda op, s=s: span(s, op)),
            state=st[s], inbox=inbox, gbox=gbox)
            for s in range(S)]
        for _tick, s, instr in sobj.events:
            execs[s].execute(instr)

        # gradient assembly: chunk sums / M, embeds tied across the
        # first and last stages (their grads ADD — one logical weight)
        layer_grads = [None] * C
        emb_grad = None
        lnf_grad = None
        losses = []
        rep_full = NamedSharding(self.mesh, P())
        for s in range(S):
            losses.extend(st[s].losses)
            for v, g in st[s].acc.items():
                # chunk grads live on their stage's sub-mesh; pull
                # them onto the full mesh so the concatenation along
                # the layer axis sees one device set
                layer_grads[v] = jax.device_put(g["layers"], rep_full)
                if "embed" in g:
                    ge = jax.device_put(g["embed"], rep_full)
                    emb_grad = ge if emb_grad is None \
                        else jax.tree_util.tree_map(jnp.add, emb_grad,
                                                    ge)
                if "ln_final" in g:
                    lnf_grad = jax.device_put(g["ln_final"], rep_full)
        grads = {
            "embed": emb_grad / M,
            "layers": jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(
                    [jnp.asarray(x) for x in xs], axis=0) / M,
                *layer_grads),
            "ln_final": _tree_div(lnf_grad, M),
        }
        grads = jax.tree_util.tree_map(
            lambda g, p: jnp.asarray(g, dtype=p.dtype) if hasattr(
                p, "dtype") else g, grads, params)
        loss = jnp.mean(jnp.stack([jnp.asarray(l) for l in losses]))

        import optax
        updates, opt_state = self.optimizer.update(
            grads, state["opt_state"], params)
        new_params = optax.apply_updates(params, updates)
        try:
            _pp_metrics(tag, sobj.bubble_fraction())
        except Exception:  # noqa: BLE001 — telemetry never fails a step
            pass
        return {"params": new_params, "opt_state": opt_state,
                "step": state["step"] + 1}, loss


def _live_engine():
    from ..common import basics

    return getattr(basics, "_engine", None)


def _live_engine_config():
    eng = _live_engine()
    return eng.config if eng is not None else None


def make_mpmd_lm_train_step(mesh, cfg, spec, optimizer=None, *,
                            learning_rate=1e-3,
                            attention_fn_factory=None):
    """(init, step, jit_step, tok_sharding) over the MPMD runtime —
    the same contract as make_lm_train_step, so callers flip between
    the fused-scan paths and the explicit-schedule runtime with one
    argument.  ``jit_step`` returns the runtime's step callable: it
    is not one jitted program (that is the point — the schedule is
    runtime data), but every chunk program inside it is compiled once
    and cached."""
    import optax

    optimizer = optimizer or optax.adamw(learning_rate)
    if isinstance(spec, dict):
        spec = PipelineSpec(**spec)
    rt = LocalPipelineRuntime(mesh, cfg, spec, optimizer,
                              attention_fn_factory=attention_fn_factory)

    def init(rng, sample_tokens):
        return rt.init(rng, sample_tokens)

    def step(state, tokens):
        return rt.step(state, tokens)

    def jit_step(state):
        return rt.step, state

    return init, step, jit_step, rt.tok_sharding()


# ---------------------------------------------------------------------------
# engine-backed (multi-process) runtime


class MpmdWorker:
    """One rank's view of a dp×pp (or dp×tp×pp with proc-local tp)
    MPMD pipeline job.

    Construction is collective and deterministic: every rank carves
    the same stage partition (common/topology.carve_stage_ranks — pp
    lands on the cross-host hop when the host map allows) and
    registers the same process sets in the same order:

    * one per-stage set (the dp gradient-reduce domain),
    * one adjacent-pair set per (stage boundary, dp index) — the
      activation/gradient hop channel,
    * one {first, last} tie set per dp index when pp > 1 — the tied
      embedding's gradient sum.
    """

    def __init__(self, cfg, spec, optimizer=None, *,
                 learning_rate=1e-3):
        import optax

        from ..common import basics

        self.cfg = cfg
        self.spec = spec.resolved()
        if cfg.n_layers % self.spec.pp:
            # chunks can degrade at step time (autotune proposals),
            # pp itself cannot — fail at build, not the first step
            raise ValueError(
                f"n_layers={cfg.n_layers} not divisible into "
                f"{self.spec.pp} pipeline stages")
        self.optimizer = optimizer or optax.adamw(learning_rate)
        eng = basics.engine()
        self.eng = eng
        self.rank = basics.rank()
        self.size = basics.size()
        S = self.spec.pp
        stage_ranks, aligned = carve_stage_ranks(
            eng.topology, S, list(range(self.size)))
        if not aligned and S > 1 and eng.topology is not None \
                and eng.topology.num_hosts > 1:
            logger.warning(
                "pipeline stage boundaries cut through hosts "
                "(host_of_rank=%s, pp=%d): pp hops will ride ICI and "
                "dp reduces may cross DCN — the inverse of the "
                "intended layout", eng.topology.host_of_rank, S)
        self.stage_ranks = stage_ranks
        self.dp = len(stage_ranks[0])
        if self.spec.dp not in (1, self.dp):
            raise ValueError(
                f"spec dp={self.spec.dp} but stages are "
                f"{self.dp} ranks wide")
        self.my_stage = next(s for s, rs in enumerate(stage_ranks)
                             if self.rank in rs)
        self.dp_index = stage_ranks[self.my_stage].index(self.rank)

        from ..common.process_sets import add_process_set

        # deterministic registration order on EVERY rank: per-stage
        # sets, then pair sets per (boundary, dp index), then ties
        self.stage_sets = [add_process_set(rs) for rs in stage_ranks]
        self.pair_sets = {}
        boundaries = [(b, b + 1) for b in range(S - 1)]
        if self.spec.schedule == "interleaved" and S > 2:
            # interleaved chunks wrap: the last stage feeds chunk c+1's
            # first stage, so (0, S-1) is a live hop channel too
            boundaries.append((0, S - 1))
        for lo, hi in boundaries:
            for d in range(self.dp):
                self.pair_sets[(lo, hi, d)] = add_process_set(
                    [stage_ranks[lo][d], stage_ranks[hi][d]])
        self.tie_sets = {}
        if S > 1:
            for d in range(self.dp):
                self.tie_sets[d] = add_process_set(
                    [stage_ranks[0][d], stage_ranks[-1][d]])

        self.programs = None       # built at first step (needs chunks)
        self._schedules = {}
        self._state = None
        self._step_no = 0
        # tp inside this process: shard chunk params/activations over
        # the proc's local devices
        self.tp = max(int(self.spec.tp), 1)
        if self.tp > 1:
            from jax.sharding import Mesh

            local = jax.local_devices()
            if len(local) < self.tp:
                raise ValueError(
                    f"tp={self.tp} needs {self.tp} local devices, "
                    f"process has {len(local)}")
            self.tp_mesh = Mesh(np.array(local[: self.tp]), ("tp",))
        else:
            self.tp_mesh = None
        # ZeRO-grade weight-update sharding on the dp hop
        # (docs/parallelism.md "Weight-update sharding"): at each
        # reduce tick the chunk's gradients go out as a grouped
        # REDUCESCATTER over the stage set instead of an allreduce,
        # the optimizer updates only this rank's dim0 shard of each
        # layer leaf (layer optimizer state is ÷dp), and the updated
        # shards ALLGATHER back ASYNCHRONOUSLY — the handles resolve
        # at the NEXT step's start, so the param gather rides the
        # engine's background thread through the inter-step gap and
        # the next step's latch/staging (the reduce-tick seam's
        # overlap, extended to the weight gather).  Embed/ln_final
        # stay dense (tiny, and tied across stages).
        self.sharded = bool(getattr(eng.config, "sharded_optimizer",
                                    False)) and self.dp > 1
        if self.sharded and self.tp > 1:
            raise ValueError(
                "sharded dp updates do not compose with proc-local "
                "tp yet (the dim0 shard would cut across the tp "
                "placement); run sharded with tp=1")
        self._shard_fp = None
        self._param_ag = None     # deferred updated-param allgather

    # -- state ----------------------------------------------------------

    def init(self, rng, sample_tokens):
        """Collective: every rank initializes the FULL model from the
        same rng (the dense twin — bit-identical everywhere) and keeps
        its own slices.  Returns the number of parameters held."""
        from ..models.transformer import TransformerLM

        params = TransformerLM(self.cfg).init(
            rng, sample_tokens)["params"]
        C = self.spec.pp * (self.spec.chunks
                            if self.spec.schedule == "interleaved"
                            else 1)
        self.programs = LMStagePrograms(self.cfg, C)
        S = self.spec.pp
        mine = {}
        for v in range(C):
            if v % S == self.my_stage:
                mine[v] = self.programs.chunk_slice(params["layers"], v)
        state = {"layers": mine}
        if self.my_stage == 0 or self.my_stage == S - 1 or S == 1:
            state["embed"] = params["embed"]
        if self.my_stage == S - 1:
            state["ln_final"] = params["ln_final"]
        if self.sharded:
            import hashlib
            import json

            shapes = [list(np.shape(l)) for l in
                      jax.tree_util.tree_leaves(state["layers"])]
            self._shard_fp = hashlib.md5(json.dumps(
                ["pp-dim0", self.dp, shapes]).encode()).hexdigest()[:16]
            shard_layers = {
                v: jax.tree_util.tree_map(self._dim0_shard, lcv)
                for v, lcv in state["layers"].items()}
            state["opt"] = {k: self.optimizer.init(
                shard_layers if k == "layers" else v)
                for k, v in state.items() if k != "opt"}
            self._record_sharded_state_bytes(state)
        else:
            state["opt"] = {k: self.optimizer.init(v)
                            for k, v in state.items() if k != "opt"}
        if self.tp_mesh is not None:
            state = self._place_tp(state)
        self._state = state
        return state

    def _dim0_shard(self, arr):
        """This rank's dim0 slice of a layer leaf (the engine
        executor's exact reducescatter chunking, so the scatter
        output IS the shard)."""
        from ..core.sharded import chunk_sizes

        a = jnp.asarray(arr)
        ch = chunk_sizes(int(a.shape[0]), self.dp)
        start = sum(ch[: self.dp_index])
        return a[start:start + ch[self.dp_index]]

    def _record_sharded_state_bytes(self, state):
        """÷dp evidence for the pp runtime: bytes of the sharded
        layer optimizer state (plus the dense embed/ln tail) next to
        the dense equivalent."""
        try:
            from .. import telemetry

            def nbytes(tree):
                return sum(
                    int(np.prod(np.shape(l) or (1,))) *
                    np.dtype(getattr(l, "dtype", np.float32)).itemsize
                    for l in jax.tree_util.tree_leaves(tree))

            shard = nbytes(state["opt"])
            dense_layers = jax.eval_shape(
                self.optimizer.init, state["layers"])
            full = shard - nbytes(state["opt"]["layers"]) \
                + nbytes(dense_layers)
            telemetry.set_optimizer_state_bytes("shard", shard)
            telemetry.set_optimizer_state_bytes("full", full)
        except Exception:  # noqa: BLE001 — telemetry must never kill
            pass           # a training job

    def _place_tp(self, state):
        shd = {}
        for v, lc in state["layers"].items():
            shd[v] = jax.device_put(
                lc, _chunk_param_shardings(self.tp_mesh, lc))
        out = dict(state)
        out["layers"] = shd
        return out

    # -- one step -------------------------------------------------------

    def _latch(self, batch):
        cfg = self.eng.config
        sched = self.spec.schedule
        m = self.spec.n_micro
        chunks = self.spec.chunks
        if getattr(cfg, "pp_stages", 1) > 1:
            sched2 = normalize_schedule(
                getattr(cfg, "pp_schedule", None))
            if sched2 is not None:
                sched = sched2
            m = int(getattr(cfg, "pp_n_micro", 0)) or m
        # the engine-mode chunk layout is fixed at init (params were
        # sliced); a schedule flip that changes the chunk count is
        # snapped back
        fixed_C = self.programs.total_chunks if self.programs else None
        if fixed_C is not None:
            if sched == "interleaved" and fixed_C == self.spec.pp:
                sched = "1f1b"
            if sched != "interleaved" and fixed_C != self.spec.pp:
                sched = "interleaved"
        if sched != "interleaved":
            chunks = 1
        m = snap_n_micro(m, batch, self.spec.pp, sched)
        if sched == "interleaved" and (m < self.spec.pp
                                       or m % self.spec.pp):
            # the proposal admits no downward snap (e.g. autotune
            # swept m=2 at pp=4, PP_CHOICES has that point): snap UP
            # to the smallest batch-dividing multiple of pp — a sweep
            # proposal degrades deterministically on every rank (same
            # cfg, same batch), it never kills the step.  Only a
            # batch pp cannot divide at all is a real error.
            m = next((c for c in range(self.spec.pp, batch + 1,
                                       self.spec.pp)
                      if batch % c == 0), 0)
            if not m:
                raise ValueError(
                    f"interleaved pipeline needs a microbatch count "
                    f"divisible by pp={self.spec.pp}; batch {batch} "
                    f"admits none")
        key = (sched, m, chunks)
        if key not in self._schedules:
            self._schedules[key] = build_schedule(
                sched, self.spec.pp, m, chunks)
        return sched, m, self._schedules[key]

    def step(self, tokens):
        """One pipelined step over this dp shard's ``tokens``
        (``(B_local, S)``; the SAME shard must go to every stage of
        this dp index — stage 0 embeds it, the last stage scores it).
        Returns the job-wide mean loss on every rank."""
        from ..ops import api as hvd_ops

        state = self._state
        if state is None:
            raise RuntimeError("call init() before step()")
        # land the PREVIOUS step's overlapped updated-param allgather
        # before any forward touches the layers (sharded mode)
        self._drain_param_ag()
        state = self._state
        B = int(tokens.shape[0])
        sched, M, sobj = self._latch(B)
        tag = pp_label(sched, M)
        # latch for the engine: every gradient reduce this step
        # submits carries the tag (Request.pp_sched), cross-rank
        # validated by the engine and coordinator
        self.eng.config.pp_sched_tag = tag
        try:
            S = self.spec.pp
            C = sobj.total_chunks
            s = self.my_stage
            d = self.dp_index
            stream = sobj.streams[s]
            progs = self.programs
            tl = self.eng.timeline
            tok_np = np.asarray(tokens)
            mb_tokens = tok_np.reshape((M, B // M) + tuple(tok_np.shape[1:]))
            act_shape = (B // M, mb_tokens.shape[2], self.cfg.d_model)
            act_dtype = np.dtype(jnp.dtype(self.cfg.dtype).name) \
                if self.cfg.dtype != jnp.bfloat16 else np.dtype(np.float32)
            # bf16 activations ship as f32 on the wire (numpy fabric);
            # everything else ships native
            ships_f32 = self.cfg.dtype == jnp.bfloat16

            st = StageState()
            emb = state.get("embed")
            lnf = state.get("ln_final")
            lc = state["layers"]

            def span(op):
                if tl is None:
                    import contextlib
                    return contextlib.nullcontext()
                return tl.span(f"pp.stage{s}", op)

            def ship(arr):
                a = np.asarray(arr, np.float32) if ships_f32 \
                    else np.asarray(arr)
                return np.ascontiguousarray(a)

            def unship(arr):
                return jnp.asarray(arr, self.cfg.dtype) if ships_f32 \
                    else jnp.asarray(arr)

            step_no = self._step_no
            # the hop/reduce semantics (pair-set broadcasts, async
            # grouped reduces at the bubble ticks) live in the
            # transport; the dispatch chain in parallel/executor.py —
            # one executor shared with the local runtime and the
            # serving tier's inference pipeline
            transport = EngineTransport(
                ops=hvd_ops, stage=s, dp_index=d, rank=self.rank,
                stage_ranks=self.stage_ranks,
                pair_sets=self.pair_sets, stage_sets=self.stage_sets,
                act_shape=act_shape, act_dtype=act_dtype,
                ship=ship, unship=unship, step_no=step_no,
                dp=self.dp, sharded=self.sharded,
                shard_fp=self._shard_fp, span=span)
            ex = LMStageExecutor(
                progs=progs, emb_first=emb, emb_last=emb, lnf=lnf,
                layers=lc, mb_tok=lambda mb: jnp.asarray(mb_tokens[mb]),
                stage=s, n_stages=S, total_chunks=C,
                transport=transport, span=span, state=st)
            ex.run(stream)
            pending = transport.pending
            reduce_handles = transport.reduce_handles
            losses = st.losses

            # drain: finish overlapped reduces + sends, reduce the embeds
            M_f = float(M)
            acc = st.acc
            for v_r, field_, hs in reduce_handles:
                reduced = hvd_ops.synchronize(hs)
                g = acc[v_r]
                _, treedef = jax.tree_util.tree_flatten(g[field_])
                g[field_] = jax.tree_util.tree_unflatten(
                    treedef, [jnp.asarray(x) for x in reduced])
            if self.dp == 1:
                pass                           # nothing to average
            else:
                # embeds + ln_final were not in the overlapped groups:
                # average them over the stage set now
                for v_r, g in acc.items():
                    for k2 in ("embed", "ln_final"):
                        if k2 in g:
                            leaves, treedef = jax.tree_util.tree_flatten(
                                g[k2])
                            out = hvd_ops.grouped_allreduce(
                                [np.asarray(x, np.float32)
                                 for x in leaves],
                                op=hvd_ops.Average,
                                name=f"pp.grad.{step_no}.{v_r}.{k2}",
                                process_set=self.stage_sets[s])
                            g[k2] = jax.tree_util.tree_unflatten(
                                treedef, [jnp.asarray(x) for x in out])
            # tied embedding: SUM the two stages' (dp-averaged) grads so
            # both copies apply the identical total and stay bit-equal
            my_emb_grad = None
            for g in acc.values():
                if "embed" in g:
                    my_emb_grad = g["embed"] if my_emb_grad is None else \
                        jnp.add(my_emb_grad, g["embed"])
            if S > 1 and emb is not None:
                total = hvd_ops.allreduce(
                    np.asarray(my_emb_grad, np.float32),
                    op=hvd_ops.Sum, name=f"pp.embtie.{step_no}",
                    process_set=self.tie_sets[d])
                my_emb_grad = jnp.asarray(total)

            # optimizer update on this rank's slices
            grads = {"layers": {v: _tree_div(acc[v]["layers"], M_f)
                                for v in lc}}
            if emb is not None:
                grads["embed"] = jnp.asarray(my_emb_grad) / M_f
            if lnf is not None:
                for g in acc.values():
                    if "ln_final" in g:
                        grads["ln_final"] = _tree_div(g["ln_final"], M_f)
            import optax

            new_state = {"opt": {}}
            for k2, p in state.items():
                if k2 == "opt":
                    continue
                if self.sharded and k2 == "layers":
                    # shard update: grads["layers"] already holds the
                    # reducescattered dim0 shards; the params and
                    # optimizer state slices match by construction
                    shard_p = {v: jax.tree_util.tree_map(
                        self._dim0_shard, lcv) for v, lcv in p.items()}
                    gk = jax.tree_util.tree_map(
                        lambda g, pp_: jnp.asarray(g, pp_.dtype),
                        grads[k2], shard_p)
                    upd, opt2 = self.optimizer.update(
                        gk, state["opt"][k2], shard_p)
                    new_shard = optax.apply_updates(shard_p, upd)
                    new_state["opt"][k2] = opt2
                    # updated shards ride home ASYNC — the gather
                    # lands at the next step's start; until then the
                    # layers stay at their pre-update values, which
                    # nothing reads (the step is over)
                    self._submit_param_ag(p, new_shard)
                    new_state[k2] = p
                    continue
                gk = jax.tree_util.tree_map(
                    lambda g, pp_: jnp.asarray(g, getattr(pp_, "dtype",
                                                          jnp.float32)),
                    grads[k2], p)
                upd, opt2 = self.optimizer.update(gk, state["opt"][k2], p)
                new_state[k2] = optax.apply_updates(p, upd)
                new_state["opt"][k2] = opt2
            self._state = new_state

            # loss: the last stage owns it; broadcast job-wide so every
            # rank's training loop sees one number
            if losses:
                my_loss = float(jnp.mean(jnp.stack(
                    [jnp.asarray(l, jnp.float32) for l in losses])))
            else:
                my_loss = 0.0
            if S > 1 or self.dp > 1:
                loss_arr = hvd_ops.allreduce(
                    np.array([my_loss if s == S - 1 else 0.0], np.float32),
                    op=hvd_ops.Sum, name=f"pp.loss.{step_no}")
                loss = float(loss_arr[0]) / max(self.dp, 1)
            else:
                loss = my_loss
            for h in pending:
                hvd_ops.synchronize(h)
            self._step_no += 1
            try:
                _pp_metrics(tag, sobj.bubble_fraction())
            except Exception:  # noqa: BLE001
                pass
            return loss
        finally:
            # the tag is a STEP-scoped latch: a stale one
            # would stamp the next non-pipeline allreduce
            # (eval/checkpoint after training, an elastic
            # rejoin) and fail cross-rank validation
            self.eng.config.pp_sched_tag = None

    def _submit_param_ag(self, layers, new_shard):
        """Submit the updated-shard allgather without waiting: the
        engine's background thread moves it while the host returns
        from step() and stages the next batch — the overlap half of
        the sharded dp hop."""
        from ..ops import api as hvd_ops
        from .. import telemetry

        leaves, treedef = jax.tree_util.tree_flatten(new_shard)
        dtypes = [l.dtype for l in
                  jax.tree_util.tree_leaves(layers)]
        # f32 on the wire like the activation hops (numpy fabric);
        # dtypes restore the leaf dtype on the way back in
        h = hvd_ops.grouped_allgather_async(
            [np.ascontiguousarray(np.asarray(l, np.float32))
             for l in leaves],
            name=f"pp.param.{self._step_no}",
            process_set=self.stage_sets[self.my_stage],
            shard_fp=self._shard_fp)
        self._param_ag = (h, treedef, dtypes)
        telemetry.count_sharded_update()

    def _drain_param_ag(self):
        """Install the overlapped allgather's full updated layers
        (no-op outside sharded mode / when nothing is pending)."""
        if self._param_ag is None:
            return
        from ..ops import api as hvd_ops

        h, treedef, dtypes = self._param_ag
        self._param_ag = None
        out = hvd_ops.synchronize(h)
        if not isinstance(out, (list, tuple)):
            out = [out]
        full = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(x, dt)
                      for x, dt in zip(out, dtypes)])
        self._state["layers"] = full

    def full_params(self):
        """Gather this rank's view into the canonical params pytree
        pieces it holds (tests / checkpoint glue)."""
        self._drain_param_ag()
        out = {"layers": dict(self._state["layers"])}
        if "embed" in self._state:
            out["embed"] = self._state["embed"]
        if "ln_final" in self._state:
            out["ln_final"] = self._state["ln_final"]
        return out
