"""Device time by the two tables the step program's report gives beside
its ``scopes`` (``scope_join.report_of``): ``renamed``, the kernels the
compiler named after itself, whose path in ``scopes`` the program
recovered by dataflow, and ``collectives``, the program's collectives by
``mode`` (``sync``, the ``start`` and ``done`` of an asynchronous one
with their ``pair``, the compute fusions an asynchronous one is
``carried`` by).  Events are the leaf operations of the traced steps,
joined to the tables by instruction name as ``scope_join`` joins them.

Every reader returns ``None``, and never raises, for a run without a
trace and for a program whose report lacks the table (an older
commit)."""

from chipbench import scope_join, trace_reduce


def report_with(ctx, field):
    """The step program's report where it has ``field``, else ``None``."""
    report = scope_join.report_of(ctx) if ctx.get("trace") else None
    return report if report is not None and field in report else None


def renamed_ms(ops, scopes, renamed, trace_steps, prefix, phases):
    """Milliseconds per traced step, the mean over the chips, of the
    events whose instruction is in ``renamed`` under a compiler's name
    that starts with ``prefix`` and whose recovered path lies in one of
    ``phases`` (``scope_join.phase_of``)."""
    chosen = {
        name for name, own in renamed.items()
        if own.startswith(prefix) and scope_join.phase_of(
            scope_join.step_path(scopes.get(name))) in phases}
    by_device = trace_reduce.leaf_ops(ops)
    seconds = sum(op.end - op.start for listed in by_device.values()
                  for op in listed
                  if scope_join.instruction_of(op.name) in chosen)
    return 1e3 * seconds / max(len(by_device), 1) / trace_steps


def read_renamed(ctx, prefix, phases):
    report = report_with(ctx, "renamed")
    if report is None:
        return None
    value = renamed_ms(ctx["trace"], report["scopes"], report["renamed"],
                       ctx["trace_steps"], prefix, phases)
    return value if value > 0 else None


def events_by_mode(ops, collectives, mode):
    """{device: {instruction: [Op by start]}} of the events of the
    ``collectives`` entries of one ``mode``."""
    wanted = {c["instruction"] for c in collectives if c["mode"] == mode}
    found = {}
    for device, listed in trace_reduce.leaf_ops(ops).items():
        mine = found[device] = {}
        for op in listed:
            instruction = scope_join.instruction_of(op.name)
            if instruction in wanted:
                mine.setdefault(instruction, []).append(op)
    return found


def mode_ms(ops, collectives, trace_steps, mode, over=max):
    """Milliseconds per traced step of the events of one ``mode``, on
    the chip where it is longest (``over=max``) or any other summary
    ``over`` the chips' seconds (``statistics.fmean``)."""
    seconds = [sum(op.end - op.start for listed in mine.values()
                   for op in listed)
               for mine in events_by_mode(ops, collectives, mode).values()]
    return 1e3 * over(seconds) / trace_steps if seconds else 0.0


def in_flight_ms(ops, collectives, trace_steps):
    """Milliseconds per traced step, on the chip where it is longest,
    of the union of the intervals from each ``start`` event to the end
    of the next event of its ``pair`` (a pair in a loop's body runs once
    an iteration: the k-th start with the k-th done)."""
    pair = {c["instruction"]: c["pair"] for c in collectives
            if c["mode"] == "start" and c["pair"]}
    starts = events_by_mode(ops, collectives, "start")
    dones = events_by_mode(ops, collectives, "done")
    worst = 0.0
    for device, mine in starts.items():
        intervals = []
        for instruction, listed in mine.items():
            closing = iter(dones[device].get(pair.get(instruction), ()))
            for op in listed:
                done = next((d for d in closing if d.start >= op.start),
                            None)
                if done is None:
                    break
                intervals.append((op.start, done.end))
        worst = max(worst, trace_reduce.union_seconds(intervals))
    return 1e3 * worst / trace_steps


def read_collectives(ctx, reduce, **how):
    """``reduce(ops, collectives, trace_steps, **how)``, ``None`` where
    the report has no ``collectives`` or nothing of the kind ran."""
    report = report_with(ctx, "collectives")
    if report is None or not report["collectives"]:
        return None
    value = reduce(ctx["trace"], report["collectives"], ctx["trace_steps"],
                   **how)
    return value if value > 0 else None
