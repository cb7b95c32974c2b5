"""What a compiled program says about its own start-up and make-up:
the first call by compile stage, and the reports of the programs that
had their first call under the process-current registry.

**Compile stages.**  jax announces how long it traced, lowered and
compiled through ``jax.monitoring``.  One process-wide listener
(registered at the first first-call, never again) keeps those
announcements only while the announcing thread is inside
:class:`first_call`, which ``ops/compiled._TimedFirstCall`` holds open
around a program's first invocation.  The announcements nest (tracing
a step traces every jitted function inside it, and a concrete value
computed while tracing runs a whole trace-lower-compile of its own),
so they are kept as intervals and each instant of the first call is
booked to ONE stage, the innermost by rank: backend compile over
lowering over tracing.  The four ``horovod_compile_*_seconds_total``
families therefore add up to no more than
``horovod_compile_seconds_total``; what is left of it is the first
execution.

**Program reports.**  ``program_reports()`` is the twin of
``metrics()`` for what a scrape cannot carry: each program's table
from instruction name to ``op_name`` path (the join key between a
device trace and the ``jax.named_scope`` names), its memory and cost
account.  Nothing is computed until someone asks.
"""

import collections
import re
import threading
import time

from .registry import registry as _current_registry

# stage ranks: the higher one owns an instant that several cover
_TRACE, _LOWER, _BACKEND = 0, 1, 2
_STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": _TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": _LOWER,
    "/jax/core/compile/backend_compile_duration": _BACKEND,
}
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# jax records this one where it WRITES an entry, not where it misses
_CACHE_WRITE_EVENT = "/jax/compilation_cache/cache_misses"

#: how many programs' report handles one registry keeps (the newest)
KEPT_PROGRAMS = 8

_thread = threading.local()
_listener_lock = threading.Lock()
_listening = False


def _on_duration(event, duration, **_):
    call = getattr(_thread, "first_call", None)
    if call is None:
        return
    stage = _STAGE_OF_EVENT.get(event)
    if stage is not None:
        # jax announces a duration as its block ends: now is the end
        end = time.perf_counter()
        call.spans.append((stage, end - duration, end))
    elif event == _CACHE_READ_EVENT:
        call.cache_read += duration


def _on_event(event, **_):
    call = getattr(_thread, "first_call", None)
    if call is None:
        return
    if event == _CACHE_HIT_EVENT:
        call.cache_hits += 1
    elif event == _CACHE_WRITE_EVENT:
        call.cache_writes += 1


def _listen():
    global _listening
    if _listening:
        return
    with _listener_lock:
        if not _listening:
            import jax

            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _listening = True


def exclusive_seconds(spans, n_stages=3):
    """[seconds per stage] of ``(stage, start, end)`` intervals, each
    instant booked once, to the highest stage that covers it."""
    edges = sorted([(start, 1, stage) for stage, start, _ in spans]
                   + [(end, -1, stage) for stage, _, end in spans])
    open_, out, last = [0] * n_stages, [0.0] * n_stages, None
    for at, step, stage in edges:
        if last is not None and at > last:
            owner = max((s for s in range(n_stages) if open_[s]),
                        default=None)
            if owner is not None:
                out[owner] += at - last
        open_[stage] += step
        last = at
    return out


class first_call:
    """Held open around a compiled program's first invocation: the
    compile stages jax announces on this thread meanwhile land in the
    ``horovod_compile_*`` families of the process-current registry."""

    def __enter__(self):
        _listen()
        self.spans = []
        self.cache_read = 0.0
        self.cache_hits = self.cache_writes = 0
        self._outer = getattr(_thread, "first_call", None)
        _thread.first_call = self
        return self

    def __exit__(self, *exc):
        _thread.first_call = self._outer
        trace, lower, backend = exclusive_seconds(self.spans)
        # the cache's read is announced from inside the backend block
        backend = max(backend - self.cache_read, 0.0)
        from .. import telemetry as t

        reg = _current_registry()
        for name, help_text, amount in (
                (t.COMPILE_TRACE_SECONDS_FAMILY,
                 t.COMPILE_TRACE_SECONDS_HELP, trace),
                (t.COMPILE_LOWER_SECONDS_FAMILY,
                 t.COMPILE_LOWER_SECONDS_HELP, lower),
                (t.COMPILE_BACKEND_SECONDS_FAMILY,
                 t.COMPILE_BACKEND_SECONDS_HELP, backend),
                (t.COMPILE_CACHE_READ_SECONDS_FAMILY,
                 t.COMPILE_CACHE_READ_SECONDS_HELP, self.cache_read),
                (t.COMPILE_CACHE_HITS_FAMILY,
                 t.COMPILE_CACHE_HITS_HELP, self.cache_hits),
                (t.COMPILE_CACHE_WRITES_FAMILY,
                 t.COMPILE_CACHE_WRITES_HELP, self.cache_writes)):
            reg.counter(name, help_text).inc(amount)
        return False


_INSTRUCTION = re.compile(
    r"^\s*(ROOT\s+)?%?([\w.\-]+)\s+=\s")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERATION = re.compile(r" ([a-z][\w\-]*)\((?:[^%]*%([\w.\-]+))?")
# operations that only move another instruction's result
_MOVES = frozenset(("copy", "copy-start", "copy-done", "bitcast",
                    "get-tuple-element"))


def instruction_scopes(hlo_text):
    """{instruction name: ``op_name`` path} for every instruction of an
    HLO module's text, fused ones included.  An instruction that
    carries no ``op_name`` of its own is booked, where it calls a
    computation (a fusion the compiler made), to that computation's
    root instruction and, where the root has none either, to the first
    instruction in it that has one; and where it only moves another's
    result (the copies and their asynchronous halves that the compiler
    inserts, a bitcast, an element of a tuple), to the instruction that
    made the result.  What is left reads ``""``."""
    scopes, calls, moves = {}, {}, []
    roots, firsts, computation = {}, {}, None
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        if not found:
            opened = _COMPUTATION.match(line)
            if opened:
                computation = opened.group(1)
            continue
        is_root, name = found.groups()
        op_name = _OP_NAME.search(line)
        op_name = op_name.group(1) if op_name else ""
        scopes[name] = op_name
        if is_root:
            roots[computation] = op_name
        if op_name:
            firsts.setdefault(computation, op_name)
        elif (called := _CALLS.search(line)):
            calls[name] = called.group(1)
        elif (op := _OPERATION.search(line, found.end())) \
                and op.group(1) in _MOVES and op.group(2):
            moves.append((name, op.group(2)))
    for name, called in calls.items():
        scopes[name] = roots.get(called) or firsts.get(called, "")
    for name, made_by in moves:     # in the text's order: operands first
        scopes[name] = scopes.get(made_by, "")
    return scopes

def executable_report(compiled, text, scopes):
    """The report of one compiled program (``jax.stages.Compiled``)
    whose optimized text is ``text`` and whose table is ``scopes``:
    see ``ops/compiled._TimedFirstCall.report``."""
    memory = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return {
        "module": re.match(r"HloModule\s+([\w.\-]+)", text).group(1),
        "scopes": scopes,
        "memory": {kind: int(getattr(memory, kind + "_size_in_bytes", 0)
                             or 0)
                   for kind in ("argument", "output", "temp", "alias",
                                "generated_code")},
        "cost": {"flops": float(cost.get("flops", 0.0)),
                 "bytes_accessed": float(cost.get("bytes accessed", 0.0))},
    }


def keep_program(program):
    """Remember ``program`` (anything with a ``report()``) on the
    process-current registry, so that ``program_reports()`` can ask it
    later: also after ``hvd.shutdown()``, until the next ``hvd.init()``
    installs a fresh registry.  The newest ``KEPT_PROGRAMS`` stay."""
    reg = _current_registry()
    kept = getattr(reg, "_kept_programs", None)
    if kept is None:
        kept = reg._kept_programs = collections.deque(
            maxlen=KEPT_PROGRAMS)
    kept.append(program)


def program_reports():
    """[report] of the compiled programs that had their first call
    under the process-current registry (the newest ``KEPT_PROGRAMS``),
    oldest first; each is what ``step.report()`` returns.  Computed
    here, on demand: every report lowers and compiles its program
    once more (a read of the persistent cache where one is placed) and
    loads a second copy of the executable while it is read."""
    kept = getattr(_current_registry(), "_kept_programs", ())
    return [program.report() for program in list(kept)]
