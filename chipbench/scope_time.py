"""Device time under a named scope of the step program, for the readers
that ``scope_join.split``'s fixed parts do not cover: the leaf
operations of the traced steps whose ``op_name`` path, as the program's
own table gives it (``scope_join.report_of``), holds the scope as a
component (``.../moe/route/dot_general``, ``transpose(jvp(experts))``),
booked as ``scope_join`` books them (a fusion whole, to its root's
path; nothing the join leaves unattributed).  Returns ``None``, and
never raises, for a program without the names."""

import re

from chipbench import scope_join, trace_reduce


def component(*names):
    """A regex for a path that holds one of ``names`` as a component,
    bare or inside jax's ``jvp(...)`` / ``transpose(...)`` wrappers."""
    return r"(?:^|[/(])(?:" + "|".join(map(re.escape, names)) + r")(?=[/)]|$)"


def under(*scopes):
    """A regex for a path that holds ``scopes`` as components in that
    order, anything between them."""
    return ".*".join(component(s) for s in scopes)


def split_ms(ops, table, trace_steps, patterns):
    """{name: milliseconds per traced step, the mean over the chips, of
    the leaf operations whose path matches ``patterns[name]``}."""
    compiled = {k: re.compile(p) for k, p in patterns.items()}
    by_device = trace_reduce.leaf_ops(ops)
    seconds = dict.fromkeys(patterns, 0.0)
    for listed in by_device.values():
        for op in listed:
            path = table.get(scope_join.instruction_of(op.name))
            if path is None:
                continue
            path = scope_join.step_path(path)
            if scope_join.phase_of(path) == "unattributed":
                continue
            for name, pattern in compiled.items():
                if pattern.search(path):
                    seconds[name] += op.end - op.start
    scale = 1e3 / max(len(by_device), 1) / trace_steps
    return {k: v * scale for k, v in seconds.items()}


def ms_per_step(ctx, pattern):
    """Milliseconds per traced step under ``pattern``, or ``None`` where
    there is no trace, no table, or nothing ran there."""
    report = scope_join.report_of(ctx) if ctx.get("trace") else None
    if report is None:
        return None
    value = split_ms(ctx["trace"], report["scopes"], ctx["trace_steps"],
                     {"it": pattern})["it"]
    return value if value > 0 else None
