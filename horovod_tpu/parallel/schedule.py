"""Pipeline schedules as explicit per-rank instruction streams.

pipeline.py's GPipe runs the whole pipeline as ONE fused scan — the
schedule is baked into the program and cannot overlap anything with
the bubbles.  Here the schedule is runtime data, the MPMD formulation
of arXiv:2412.14374: each physical stage executes a deterministic
stream of forward / backward / send / recv / reduce ticks, and the
runtime (runtime.py) interprets the stream against per-stage compiled
programs.  That makes 1F1B and interleaved-1F1B expressible (their
backward passes start before the last forward finishes — impossible
to write as a single reverse-mode scan), and it opens the bubbles:
``reduce`` ticks fire the dp-dimension gradient collectives through
the engine's async submit exactly where the stage would otherwise
idle.

Each schedule is generated in two steps: the per-stage COMPUTE ORDER
comes from the textbook closed forms (GPipe fill-drain; 1F1B warmup =
``S-s-1`` forwards then strict alternation; interleaved-1F1B =
Megatron's virtual-microbatch walk over ``n_chunks`` model chunks per
stage, warmup ``2(S-s-1) + (V-1)S``), and a dependency-driven timing
simulation then assigns every instruction its tick — yielding the
makespan (bubble fraction) and a global event order that is a
topological order of the data dependencies.  Each stage's stream is a
subsequence of that order, so executing the streams asynchronously —
blocking receives, non-blocking sends — can never deadlock.
"""

from dataclasses import dataclass, field
from typing import List, Tuple

__all__ = [
    "SCHEDULES", "PP_N_MICRO_CHOICES", "PP_CHOICES", "Instr",
    "Schedule", "build_schedule", "bubble_fraction",
    "normalize_schedule", "pp_label", "parse_pp_label",
]

#: schedule vocabulary, in autotune-grid order (core/autotune.py)
SCHEDULES = ("gpipe", "1f1b", "interleaved")

_SCHEDULE_ALIASES = {
    None: None, "": None,
    "gpipe": "gpipe", "fill-drain": "gpipe", "filldrain": "gpipe",
    "1f1b": "1f1b", "pipedream": "1f1b",
    "interleaved": "interleaved", "interleaved-1f1b": "interleaved",
    "interleaved_1f1b": "interleaved",
}

#: microbatch counts the autotuner sweeps (powers of two: every batch
#: the benchmarks run divides evenly, and the runtime snaps an
#: indivisible proposal to the nearest legal value anyway)
PP_N_MICRO_CHOICES = (2, 4, 8)

#: the autotuner's SEVENTH dimension: (schedule, n_micro) as ONE
#: categorical — a legal-pair enumeration like quantize.py's
#: WIRE_PAIR_CHOICES, swept by core/autotune.py and latched per
#: negotiation entry by the engine (Request.pp_sched)
PP_CHOICES = tuple(
    (sched, m) for sched in SCHEDULES for m in PP_N_MICRO_CHOICES)


def normalize_schedule(schedule):
    """Canonicalize a schedule spec -> None (unset) | 'gpipe' |
    '1f1b' | 'interleaved'."""
    key = schedule.strip().lower() if isinstance(schedule, str) \
        else schedule
    try:
        return _SCHEDULE_ALIASES[key]
    except KeyError:
        raise ValueError(
            f"unknown pipeline schedule {schedule!r}: expected one of "
            f"{SCHEDULES}")


def pp_label(schedule, n_micro):
    """Human/metric spelling of the autotune pair — also the
    ``Request.pp_sched`` tag the engine cross-rank-validates."""
    return f"{schedule}@{int(n_micro)}"


def parse_pp_label(label):
    sched, _, m = str(label).partition("@")
    return normalize_schedule(sched), int(m)


@dataclass(frozen=True)
class Instr:
    """One tick of a stage's instruction stream.

    ``op``:

    * ``fwd`` / ``bwd``    — run chunk ``chunk``'s forward / backward
      for microbatch ``mb``.
    * ``recv_act`` / ``send_act``   — activation hop with stage
      ``peer`` (recv precedes the fwd it feeds; send follows the fwd
      that produced it and is NON-blocking).
    * ``recv_grad`` / ``send_grad`` — the backward hop.
    * ``reduce``           — chunk ``chunk``'s gradients are complete:
      submit its dp-dimension allreduce NOW (async), overlapping the
      wire time with the remaining backward ticks / drain bubble.
    """
    op: str
    mb: int = -1
    chunk: int = 0
    peer: int = -1


@dataclass
class Schedule:
    """A built schedule: per-stage streams plus the simulator's global
    event order (the local runtime executes events; the distributed
    runtime hands each rank its stream)."""
    schedule: str
    n_stages: int
    n_micro: int
    n_chunks: int
    #: per-stage instruction streams, index = physical stage
    streams: List[List[Instr]]
    #: global execution order: (tick, stage, Instr) sorted by tick
    events: List[Tuple[int, int, Instr]]
    #: simulated makespan in ticks (one fwd or bwd = one tick)
    n_ticks: int = 0

    @property
    def total_chunks(self):
        return self.n_stages * self.n_chunks

    def bubble_fraction(self):
        """Idle fraction of the stage×tick grid — the schedule's
        analytic pipeline-bubble cost (0 for a single stage)."""
        if self.n_ticks == 0:
            return 0.0
        work = 2 * self.n_micro * self.n_chunks   # per stage
        return 1.0 - work / float(self.n_ticks)

    def chunk_stage(self, chunk):
        """Physical stage hosting global chunk index ``chunk``
        (chunk-major round-robin: rank s owns chunks s, s+S, ...)."""
        return chunk % self.n_stages


def _compute_order(schedule, n_stages, n_micro, n_chunks, s):
    """Stage ``s``'s total order of compute ticks as
    ``(kind, chunk, mb)`` triples — the closed-form schedules."""
    S, M, V = n_stages, n_micro, n_chunks
    if schedule == "gpipe":
        return ([("fwd", 0, m) for m in range(M)]
                + [("bwd", 0, m) for m in range(M)])
    if schedule == "1f1b":
        w = min(S - s - 1, M)
        order = [("fwd", 0, m) for m in range(w)]
        for i in range(M - w):
            order.append(("fwd", 0, w + i))
            order.append(("bwd", 0, i))
        for i in range(max(M - w, 0), M):
            order.append(("bwd", 0, i))
        return order

    # interleaved-1F1B (Megatron get_model_chunk_id walk): virtual
    # microbatch slot k runs chunk (k % (S*V)) // S ascending on the
    # forward walk, descending on the backward walk, with microbatch
    # (k // (S*V)) * S + k % S — groups of S microbatches stream
    # through chunk 0, then chunk 1, ...
    total = M * V

    def f_slot(k):
        kg = k % (S * V)
        return (kg // S, (k // (S * V)) * S + kg % S)

    def b_slot(k):
        kg = k % (S * V)
        return (V - 1 - kg // S, (k // (S * V)) * S + kg % S)

    w = min(2 * (S - s - 1) + (V - 1) * S, total)
    order = [("fwd",) + f_slot(k) for k in range(w)]
    for i in range(total - w):
        order.append(("fwd",) + f_slot(w + i))
        order.append(("bwd",) + b_slot(i))
    for i in range(max(total - w, 0), total):
        order.append(("bwd",) + b_slot(i))
    return order


# hvdlint: seam[determinism]
def build_schedule(schedule, n_stages, n_micro, n_chunks=1):
    """Build the per-stage instruction streams for one training step.

    Deterministic pure function of its arguments — every rank builds
    the SAME streams locally (the declared determinism seam: two ranks
    disagreeing here would exchange mismatched sends/recvs and either
    deadlock or silently mis-train; the engine additionally
    cross-validates the latched ``schedule@n_micro`` tag on every
    gradient reduce).

    * ``gpipe``: all ``n_micro`` forwards, then all backwards — the
      fill-drain fallback, bubble ≈ (S-1)/(M+S-1).
    * ``1f1b``: stage s runs ``min(S-s-1, M)`` warmup forwards, then
      alternates one-forward-one-backward; steady-state memory is
      O(S-s) activations instead of O(M).
    * ``interleaved``: 1F1B over ``n_chunks`` model chunks per stage
      (virtual stage v = chunk*S + s runs on stage s); needs
      ``n_micro % n_stages == 0`` and ``n_chunks >= 2``.  Bubble
      shrinks by ~1/n_chunks at the cost of 2(V-1) extra hops per
      microbatch.

    Every stream ends each chunk's backward run with a ``reduce``
    tick placed at the earliest point that chunk's gradient is
    complete — inside the drain bubble for every stage but the first.
    """
    schedule = normalize_schedule(schedule) or "1f1b"
    n_stages = int(n_stages)
    n_micro = int(n_micro)
    n_chunks = int(n_chunks)
    if n_stages < 1:
        raise ValueError(f"n_stages must be >= 1, got {n_stages}")
    if n_micro < 1:
        raise ValueError(f"n_micro must be >= 1, got {n_micro}")
    if schedule == "interleaved":
        if n_chunks < 2:
            raise ValueError(
                "interleaved needs n_chunks >= 2 model chunks per "
                f"stage (got {n_chunks}); use '1f1b' for one chunk")
        if n_micro % n_stages != 0:
            raise ValueError(
                f"interleaved needs n_micro ({n_micro}) divisible by "
                f"n_stages ({n_stages})")
    elif n_chunks != 1:
        raise ValueError(
            f"schedule {schedule!r} runs one chunk per stage "
            f"(got n_chunks={n_chunks})")

    C = n_stages * n_chunks              # virtual pipeline depth
    M = n_micro

    def owner(v):
        return v % n_stages

    # dependency-driven timing of the closed-form per-stage orders:
    # each stage executes its order strictly in sequence, one compute
    # tick per simulated tick, blocking until the instruction's data
    # dependency has completed at an EARLIER tick (transfers land
    # between ticks).  Completion ticks; -1 = not done.
    orders = [_compute_order(schedule, n_stages, n_micro, n_chunks, s)
              for s in range(n_stages)]
    fwd_done = [[-1] * M for _ in range(C)]
    bwd_done = [[-1] * M for _ in range(C)]
    cursor = [0] * n_stages

    def ready(s, t):
        kind, c, m = orders[s][cursor[s]]
        v = c * n_stages + s
        if kind == "fwd":
            return v == 0 or (0 <= fwd_done[v - 1][m] < t)
        if fwd_done[v][m] < 0 or fwd_done[v][m] >= t:
            return False
        return v == C - 1 or (0 <= bwd_done[v + 1][m] < t)

    events = []          # (tick, stage, kind, v, m)
    done = 0
    total = 2 * C * M
    t = 0
    while done < total:
        progressed = False
        for s in range(n_stages):
            if cursor[s] >= len(orders[s]) or not ready(s, t):
                continue
            kind, c, m = orders[s][cursor[s]]
            v = c * n_stages + s
            (fwd_done if kind == "fwd" else bwd_done)[v][m] = t
            events.append((t, s, kind, v, m))
            cursor[s] += 1
            done += 1
            progressed = True
        if not progressed and done < total:
            raise RuntimeError(
                f"schedule wedged at tick {t} ({done}/{total} "
                f"instructions placed) — {schedule} S={n_stages} "
                f"M={M} V={n_chunks}")
        t += 1

    # last backward tick per (stage, chunk): the reduce goes right
    # after it
    last_bwd = {}
    for tick, s, kind, v, m in events:
        if kind == "bwd":
            c = v // n_stages
            last_bwd[(s, c)] = max(last_bwd.get((s, c), -1), tick)

    streams = [[] for _ in range(n_stages)]
    out_events = []

    def emit(tick, s, instr):
        streams[s].append(instr)
        out_events.append((tick, s, instr))

    for tick, s, kind, v, m in events:
        c = v // n_stages
        if kind == "fwd":
            if v > 0 and owner(v - 1) != s:
                emit(tick, s, Instr("recv_act", m, c, owner(v - 1)))
            emit(tick, s, Instr("fwd", m, c))
            if v < C - 1 and owner(v + 1) != s:
                emit(tick, s, Instr("send_act", m, c, owner(v + 1)))
        else:
            if v < C - 1 and owner(v + 1) != s:
                emit(tick, s, Instr("recv_grad", m, c, owner(v + 1)))
            emit(tick, s, Instr("bwd", m, c))
            if v > 0 and owner(v - 1) != s:
                emit(tick, s, Instr("send_grad", m, c, owner(v - 1)))
            if tick == last_bwd[(s, c)]:
                emit(tick, s, Instr("reduce", -1, c))

    # stable global order: tick, then emission order within the tick
    # (the list is already tick-sorted because events was)
    return Schedule(schedule=schedule, n_stages=n_stages,
                    n_micro=n_micro, n_chunks=n_chunks,
                    streams=streams, events=out_events, n_ticks=t)


def bubble_fraction(schedule, n_stages, n_micro, n_chunks=1):
    """Analytic idle fraction of the stage×tick grid for a schedule."""
    return build_schedule(schedule, n_stages, n_micro,
                          n_chunks).bubble_fraction()
