"""The join between a device trace and the program's own names: every
leaf operation of the traced steps is looked up, by its instruction's
name, in the table the step program gives of itself
(``horovod_tpu.telemetry.program_reports()``: instruction name ->
``op_name`` path, which carries the ``jax.named_scope`` names), and its
device time is booked to one phase of the step, and beside that to a
part of the model and to a kernel where its path names one.

The phases partition the leaf operations' time (``PHASES``): what the
program puts under ``hvd_step/optimizer`` and under
``hvd_step/grad_reduce`` / ``hvd_step/aux_reduce`` (``reduce``), and
inside ``hvd_step/loss_and_grad`` the recomputed forward
(``rematted_computation`` in the path: ``remat``), the rest of the
backward (``transpose(``: jax's name for the transposed ``jvp``) and
the forward (everything else there).  An operation whose instruction
the table does not hold, or whose path has no ``hvd_step/`` scope, is
``unattributed``: the honesty of the other five.  A fusion is booked as
the table books it (to its own ``op_name``, which the compiler takes
from the fusion's root, or to the root's).

Nothing here raises for a program that lacks the names: with a program
that has no ``program_reports`` (an older commit) or whose table holds
no ``hvd_step/`` scope (an executable that an older commit compiled and
the persistent cache handed back: metadata is not in the cache's key),
``split_of`` is ``None`` and every reader that uses it returns ``None``.

The arithmetic (``split``) works on plain ``Op`` tuples and a plain
table, and is tested on hand-made ones and on the recorded traces in
``tests/data`` and ``tests/data_scopes``.
"""

import re

from chipbench import trace_reduce

PHASES = ("forward", "backward", "remat", "optimizer", "reduce",
          "unattributed")
STEP_SCOPE = "hvd_step/"
_PHASE_OF_SCOPE = (
    ("hvd_step/optimizer", "optimizer"),
    ("hvd_step/grad_reduce", "reduce"),
    ("hvd_step/aux_reduce", "reduce"),
)
# parts of the model: a component of the path, as flax and
# ``jax.named_scope`` write it (``.../layers/attn/wq/dot_general``,
# ``jvp(lm_head_ce)/while/...``)
PARTS = {
    "mlp": ("mlp",),
    "attention": ("attn",),
    "loss_head": ("embed", "lm_head", "lm_head_ce"),
}
# the Pallas kernels by the scope their call sits under: the flash
# forward, and the ONE backward kernel (dq, dk and dv since PR 28), which
# kept the scope and the name of the older dk/dv kernel
KERNELS = ("flash_fwd", "flash_dkv")
STEP_CALLS = "horovod_step_calls_total"


def _component(names):
    return re.compile(
        r"(?:^|[/(])(?:" + "|".join(map(re.escape, names))
        + r")(?:[/)]|$)")


_PART_PATTERNS = {part: _component(names) for part, names in PARTS.items()}
_KERNEL_PATTERNS = {k: _component((k,)) for k in KERNELS}
_MODULE_RUN = re.compile(r"^(.*?)\(\d+\)$")


def instruction_of(op_name):
    """``fusion.378`` of ``fusion.378 = fusion f32[4096]`` (the name
    ``trace_reduce.short_name`` leaves an event)."""
    return op_name.partition(" = ")[0]


def step_path(path):
    """Of the ``;``-joined paths of a merged instruction the first that
    lies under a step scope, else the first, else ``""``."""
    paths = (path or "").split(";")
    return next((p for p in paths if STEP_SCOPE in p), paths[0])


def phase_of(path):
    """The phase of ``PHASES`` an ``op_name`` path belongs to."""
    if path is None or STEP_SCOPE not in path:
        return "unattributed"
    for scope, phase in _PHASE_OF_SCOPE:
        if scope in path:
            return phase
    if "rematted_computation" in path:
        return "remat"
    return "backward" if "transpose(" in path else "forward"


def has_step_scopes(scopes):
    return any(STEP_SCOPE in path for path in scopes.values())


def split(ops, scopes, trace_steps):
    """Milliseconds per traced step, the mean over the chips, of the
    leaf operations of ``ops`` by ``phase``, by ``part`` of the model
    and by ``kernel``, with their ``total``, and the ``unattributed``
    operations that took longest (``[[name, ms]]``)."""
    by_device = trace_reduce.leaf_ops(ops)
    phase = dict.fromkeys(PHASES, 0.0)
    part = dict.fromkeys(PARTS, 0.0)
    kernel = dict.fromkeys(KERNELS, 0.0)
    lost = {}
    for listed in by_device.values():
        for op in listed:
            seconds = op.end - op.start
            path = scopes.get(instruction_of(op.name))
            path = None if path is None else step_path(path)
            booked = phase_of(path)
            phase[booked] += seconds
            if booked == "unattributed":
                lost[op.name] = lost.get(op.name, 0.0) + seconds
                continue
            for name, pattern in _PART_PATTERNS.items():
                if pattern.search(path):
                    part[name] += seconds
                    break
            if op.name.endswith(trace_reduce.KERNEL_MARK):
                for name, pattern in _KERNEL_PATTERNS.items():
                    if pattern.search(path):
                        kernel[name] += seconds
                        break
    scale = 1e3 / max(len(by_device), 1) / trace_steps

    def per_step(seconds):
        return {k: v * scale for k, v in seconds.items()}

    phase = per_step(phase)
    return {"phase": phase, "part": per_step(part),
            "kernel": per_step(kernel), "total": sum(phase.values()),
            "unattributed": [[name, t * scale] for name, t in sorted(
                lost.items(), key=lambda kv: -kv[1])[:10]]}


def traced_module(ops):
    """The name, without the run's fingerprint, of the program that the
    devices ran longest in the trace (``jit_prog`` of
    ``jit_prog(1234)``), or ``None``."""
    seconds = {}
    for op in ops:
        if op.line == trace_reduce.MODULES_LINE:
            found = _MODULE_RUN.match(op.name)
            name = found.group(1) if found else op.name
            seconds[name] = seconds.get(name, 0.0) + op.end - op.start
    return max(seconds, key=seconds.get) if seconds else None


def report_of(ctx):
    """The step program's report of itself: of the programs the process
    kept, the one with step scopes whose module the trace ran (any with
    step scopes where there is no trace), or ``None``.  Asked once a
    run; asking compiles the program again."""
    if "_program_report" not in ctx:
        ctx["_program_report"] = _find_report(ctx.get("trace"))
    return ctx["_program_report"]


def _find_report(ops):
    from horovod_tpu import telemetry

    ask = getattr(telemetry, "program_reports", None)
    if ask is None:
        return None
    reports = [r for r in ask() if r and has_step_scopes(r["scopes"])]
    module = traced_module(ops) if ops else None
    ran = [r for r in reports if r["module"] == module]
    chosen = ran or reports
    return chosen[-1] if chosen else None


def split_of(ctx):
    """``split`` of this run's trace by this run's step program, or
    ``None`` where either is missing."""
    if "_scope_split" not in ctx:
        report = report_of(ctx) if ctx.get("trace") else None
        ctx["_scope_split"] = None if report is None else split(
            ctx["trace"], report["scopes"], ctx["trace_steps"])
    return ctx["_scope_split"]


def read(ctx, group, name):
    """One number of ``split_of``; ``None`` where there is no split or,
    for a part or a kernel, no operation of that name ran."""
    found = split_of(ctx)
    if found is None:
        return None
    value = found[group][name]
    return value if group == "phase" or value > 0 else None


def counter_delta(ctx, name):
    """What the program counter ``name`` advanced by inside the
    window."""
    counters = ctx["counters"]
    return counters["window_end"][name] - counters["window_start"][name]


def per_step_ms(ctx, name, per_rank=False):
    """The counter's seconds inside the window as milliseconds a step
    (a rank's step with ``per_rank``); ``None`` where the program counts
    no step calls (an older commit)."""
    if counter_delta(ctx, STEP_CALLS) <= 0:
        return None
    steps = ctx["window"]["steps"] * (ctx["ranks"] if per_rank else 1)
    return 1e3 * counter_delta(ctx, name) / steps
