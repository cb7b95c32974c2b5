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
_OPERATION = re.compile(r" ([a-z][\w\-]*)\(")
_REFERENCE = re.compile(r"%([\w.\-]+)")
# operations that only move another instruction's result
_MOVES = frozenset(("copy", "copy-start", "copy-done", "bitcast",
                    "get-tuple-element"))
# ... and those a walk along the dataflow passes through besides
_PASSED = _MOVES | {"tuple"}
_COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-(start|done))?$")
# what the TPU compiler's fusions around an asynchronous collective hold
_ASYNC_HALF = re.compile(r'custom_call_target="AsyncCollective(Start|Done)"')
_ARRAY = re.compile(r"\b(pred|[a-z]+(\d+)\w*)\[([\d,]*)\]")


def _operands(line, at):
    """The names of an instruction's operands; its operation's ``(`` is
    at ``at - 1`` of ``line``."""
    end = line.find(")", at)
    if line.find("(", at, end) >= 0:    # operands with their types
        depth = 1
        for end in range(at, len(line)):
            depth += (line[end] == "(") - (line[end] == ")")
            if not depth:
                break
    return _REFERENCE.findall(line, at, end)


def _bytes(result):
    """Bytes of a result type (``f32[4096,8,128]{...}``, or a tuple's
    elements together)."""
    total = 0
    for kind, bits, dims in _ARRAY.findall(result):
        count = 1
        for dim in filter(None, dims.split(",")):
            count *= int(dim)
        total += (count * (int(bits) if bits else 8) + 7) // 8
    return total


def _first_reached(start, edges, wanted, passed):
    """Breadth first from ``start`` along ``edges``: the first
    instruction that is ``wanted``, walking on only through those that
    are ``passed``; ``None`` where the walk ends without one."""
    seen, frontier = {start}, [start]
    while frontier:
        reached = []
        for name in frontier:
            for other in edges.get(name, ()):
                if other in seen:
                    continue
                seen.add(other)
                if wanted(other):
                    return other
                if passed(other):
                    reached.append(other)
        frontier = reached
    return None


def program_tables(hlo_text):
    """What an optimized HLO module's text says of its instructions:
    ``{"scopes", "renamed", "collectives"}``, the three tables of a
    program's report (``ops/compiled._TimedFirstCall.report``).

    ``scopes``: {instruction name: ``op_name`` path} for every
    instruction, fused ones included.  An instruction that
    carries no ``op_name`` of its own is booked, where it calls a
    computation (a fusion the compiler made), to that computation's
    root instruction and, where the root has none either, to the first
    instruction in it that has one; and where it only moves another's
    result (the copies and their asynchronous halves that the compiler
    inserts, a bitcast, an element of a tuple), to the instruction that
    made the result.  What is left reads ``""``.

    ``renamed``: {instruction name: the compiler's own ``op_name``} of
    the kernels the compiler put in an operation's place and named
    after themselves (a ``custom-call`` whose ``op_name`` holds no scope
    of the program, no ``/``: ``ragged-dot-none``), for which ``scopes``
    holds a path recovered by dataflow: that of the first instruction
    with a stated program path among the kernel's users (breadth first,
    through moves, tuples and other such kernels), less its last two
    components (the primitive and the user's own innermost scope, which
    need not be the kernel's), then the compiler's name.  A kernel with
    no such user keeps its own string and is not listed.

    ``collectives``: one entry for every instruction of a computation
    that runs (not a fused one) that is a collective or calls a
    computation that holds one: ``{"instruction", "kind", "bytes",
    "mode", "pair", "path"}``.  ``kind`` is the collective's opcode and
    ``bytes`` those of the collective's own result; ``mode`` is
    ``"sync"`` for a bare collective, ``"start"`` / ``"done"`` for the
    halves of an asynchronous one (an ``all-reduce-start``, or a
    fusion of the TPU compiler's around an ``AsyncCollectiveStart`` /
    ``AsyncCollectiveDone`` call: ``async-collective-start.N`` in a
    trace), ``"carried"`` for a compute fusion that holds a step of an
    asynchronous collective; ``pair`` is the other half's name
    (the ``done`` that the start's state reaches through the carried
    fusions), else ``None``; ``path`` is the instruction's entry of
    ``scopes``."""
    lines = hlo_text.splitlines()
    scopes, unnamed_callers, moves = {}, [], []
    roots, firsts, computation = {}, {}, None
    opcodes, operands_at, called = {}, {}, {}
    kernels, collective, halves = [], {}, {}
    for number, line in enumerate(lines):
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
        op = _OPERATION.search(line, found.end())
        opcode = opcodes[name] = op.group(1) if op else ""
        if op:
            operands_at[name] = (number, op.end())
        if is_root:
            roots[computation] = op_name
        calls = _CALLS.search(line)
        if calls:
            called[name] = calls.group(1)
        if op_name:
            firsts.setdefault(computation, op_name)
        elif calls:
            unnamed_callers.append(name)
        elif opcode in _MOVES:
            made_by = _REFERENCE.search(line, op.end())
            if made_by:
                moves.append((name, made_by.group(1)))
        if opcode == "custom-call":
            if op_name and "/" not in op_name:
                # a kernel the compiler put in an operation's place
                # and named after itself
                kernels.append(name)
            elif (half := _ASYNC_HALF.search(line, op.end())):
                halves[computation] = half.group(1).lower()
        elif _COLLECTIVE.match(opcode):
            collective[name] = (computation, line[found.end():op.start()])
    for name in unnamed_callers:
        scopes[name] = roots.get(called[name]) \
            or firsts.get(called[name], "")

    users = {}      # the dataflow, read only where something walks it
    if kernels or collective:
        for name, (number, at) in operands_at.items():
            for made_by in _operands(lines[number], at):
                users.setdefault(made_by, []).append(name)

    renamed = {}
    compiler_named = frozenset(kernels)
    for name in kernels:
        user = _first_reached(
            name, users,
            lambda n: "/" in scopes.get(n, "") and n not in compiler_named,
            lambda n: opcodes.get(n) in _PASSED or n in compiler_named)
        enclosing = user and scopes[user].split(";")[0].split("/")[:-2]
        if enclosing:
            renamed[name] = scopes[name]
            scopes[name] = "/".join(enclosing + [scopes[name]])
    for name, made_by in moves:     # in the text's order: operands first
        scopes[name] = scopes.get(made_by, "")
    return {"scopes": scopes, "renamed": renamed,
            "collectives": _collectives(
                collective, halves, called, opcodes, users, scopes)}


def _collectives(collective, halves, called, opcodes, users, scopes):
    """The ``collectives`` table of ``program_tables``, from
    ``collective`` ({collective instruction: (its computation, its
    result type)}), ``halves`` ({computation around an asynchronous
    collective's call: ``"start"`` or ``"done"``}) and ``called``
    ({instruction: the computation it calls})."""
    fused = frozenset(called.values())
    held = {}       # fused computation: the first collective in it
    for name, (computation, _) in collective.items():
        if computation in fused:
            held.setdefault(computation, name)
    entries, mode = [], {}
    for name in opcodes if collective else ():
        if name in collective and collective[name][0] not in fused:
            inside = name
        else:
            inside = held.get(called.get(name))
            if inside is None:
                continue
        kind, half = _COLLECTIVE.match(opcodes[inside]).groups()
        mode[name] = (half or "sync") if inside is name \
            else halves.get(called[name], "carried")
        entries.append({
            "instruction": name, "kind": kind,
            "bytes": _bytes(collective[inside][1]), "mode": mode[name],
            "pair": None, "path": scopes[name]})
    pair = {}
    for name, its in mode.items():
        if its == "start":
            done = _first_reached(
                name, users, lambda n: mode.get(n) == "done",
                lambda n: opcodes.get(n) in _PASSED
                or mode.get(n) == "carried")
            if done is not None:
                pair[name], pair[done] = done, name
    for entry in entries:
        entry["pair"] = pair.get(entry["instruction"])
    return entries


def instruction_scopes(hlo_text):
    """``program_tables(hlo_text)["scopes"]``."""
    return program_tables(hlo_text)["scopes"]


def executable_report(compiled, text, tables):
    """The report of one compiled program (``jax.stages.Compiled``)
    whose optimized text is ``text`` and whose tables
    (``program_tables(text)``) are ``tables``: see
    ``ops/compiled._TimedFirstCall.report``."""
    memory = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return {
        "module": re.match(r"HloModule\s+([\w.\-]+)", text).group(1),
        **tables,
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
