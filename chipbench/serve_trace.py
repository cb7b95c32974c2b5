"""From a served run's device trace and its requests' stamps to the
numbers the serving readers share.  The trace holds a compiled program's
runs on the line ``XLA Modules`` and the host's dispatches of them
(``PjitFunction(_decode_fwd)``) on the host's threads; the adapter says
which names are prefill, ingest and decode.  The harness's
own annotation of the traced span is on the host's plane with the
trace's clock, and was entered at a known reading of the host's clock:
that ties the requests' stamps to the trace."""

from chipbench import trace_reduce

KINDS = ("prefill", "ingest", "decode")


def traced_span(ctx):
    """(start, end) of the traced span on the trace's clock and the
    offset that takes a host stamp onto it; None without a served run's
    trace."""
    serve = ctx.get("serve")
    if not serve or not ctx.get("trace") or not serve["spans"]["trace"]:
        return None
    found = [(s, e) for name, s, e in trace_reduce.host_spans(ctx["trace"])
             if name == serve["traced_span_name"]]
    if not found:
        return None
    start, end = found[0]
    return start, end, start - serve["spans"]["trace"][0]


DISPATCH = "PjitFunction("
# a run begins within this of its dispatch, the clocks' offset taken off
NEAR = 1e-3


def _dispatches(ops, names):
    """[(start, the dispatched function's name, its kind or None)] by
    start, each once (the trace holds a host event on two lines, a
    fraction of a microsecond apart)."""
    found = sorted((op.start, op.name[len(DISPATCH):-1]) for op in ops
                   if op.line == trace_reduce.HOST_LINE
                   and op.name.startswith(DISPATCH))
    once = [mark for i, mark in enumerate(found)
            if not i or mark[1] != found[i - 1][1]
            or mark[0] - found[i - 1][0] > 5e-6]
    return [(start, name, next((kind for kind, fn in names.items()
                                if fn == name), None))
            for start, name in once]


def _nearest(starts, at):
    import bisect

    i = bisect.bisect_left(starts, at)
    return min((j for j in (i - 1, i) if 0 <= j < len(starts)),
               key=lambda j: abs(starts[j] - at))


def program_kinds(ops, names):
    """{a module's name on the trace: its kind}.  The trace calls every
    one of these programs ``jit__unknown(<fingerprint>)`` (they are
    jitted ``functools.partial``s), so a fingerprint's kind is learnt
    from the host's side: the device runs programs in the order of the
    host's ``PjitFunction(<name>)`` dispatches, one run a dispatch, and
    each run is credited to the dispatch that began nearest to it; a
    fingerprint takes the kind most of its runs were credited to (one
    program a bucket, many runs a program).  The device's clock and the
    host's lie about a millisecond apart in a trace, as far as two of a
    tick's dispatches: the offset is the median one between the runs
    of the small programs BOTH sides name (``jit_broadcast_in_dim`` and
    its dispatch: the batcher's own index and cast of a tick's
    operands; every trace of the chip has them, and one with fewer than
    three tells no program apart).  A fingerprint that is no program of
    ``names`` gets ``None``."""
    import collections
    import statistics

    marks = _dispatches(ops, names)
    if not marks:
        return {}
    starts = [start for start, _, _ in marks]
    modules = [op for op in ops
               if op.line == trace_reduce.MODULES_LINE and op.device == 0]
    by_name = collections.defaultdict(list)
    for start, name, _ in marks:
        by_name[name].append(start)
    offsets = []
    for op in modules:
        named = by_name.get(op.name[len("jit_"):].split("(")[0])
        if named:
            offsets.append(named[_nearest(named, op.start)] - op.start)

    if len(offsets) < 3:
        return {}
    skew = statistics.median(offsets)
    # a run whose own name a dispatch bears needs no vote; one with no
    # dispatch within ``NEAR`` (dispatched before the trace began) casts
    # none
    votes = collections.defaultdict(collections.Counter)
    for op in modules:
        if op.name[len("jit_"):].split("(")[0] in by_name:
            continue
        i = _nearest(starts, op.start + skew)
        if abs(starts[i] - op.start - skew) <= NEAR:
            votes[op.name][marks[i][2]] += 1
    return {name: count.most_common(1)[0][0]
            for name, count in votes.items()}


def program_runs(ops, names, span=None):
    """{kind: [(start, end)] by start} of the programs' runs on the
    first chip, those that lie wholly inside ``span`` if one is given."""
    kinds = program_kinds(ops, names)
    runs = {kind: [] for kind in names}
    for op in ops:
        if op.line != trace_reduce.MODULES_LINE or op.device != 0 \
                or kinds.get(op.name) is None:
            continue
        if span is None or (span[0] <= op.start and op.end <= span[1]):
            runs[kinds[op.name]].append((op.start, op.end))
    for listed in runs.values():
        listed.sort()
    return runs


def runs_in_span(ctx):
    """(runs by kind inside the traced span, the span with its offset),
    or None."""
    span = traced_span(ctx)
    if span is None:
        return None
    return program_runs(ctx["trace"], ctx["serve"]["programs"],
                        span[:2]), span


def seconds_of(runs):
    return sum(end - start for start, end in runs)


def stamps_in(ctx, span, first):
    """[(request, index of the token)] of the tokens stamped inside
    ``span`` (host clock): the first tokens of their requests (which a
    prefill chose) if ``first``, else the others (which decode did)."""
    out = []
    for r in ctx["serve"]["requests"]:
        for i, stamp in enumerate(r["stamps"]):
            if span[0] <= stamp < span[1] and (i == 0) == first:
                out.append((r, i))
    return out


def prefilled(ctx, runs, span):
    """The requests whose prompts the span's prefill runs took, a
    request a run, or None where one cannot be told: a run's request is
    the one whose first token was stamped first after the run began
    (prefills run one after another, each followed by its ingest and
    its token's stamp before the next begins; a stamp may fall after
    the span's end, so every request is looked at, not the span's)."""
    import bisect

    firsts = sorted((r["stamps"][0] + span[2], i)
                    for i, r in enumerate(ctx["serve"]["requests"])
                    if r["stamps"])
    times = [at for at, _ in firsts]
    picked = [bisect.bisect_left(times, start)
              for start, _ in runs["prefill"]]
    if len(set(picked)) != len(picked) or any(
            i >= len(firsts) for i in picked):
        return None
    return [ctx["serve"]["requests"][firsts[i][1]] for i in picked]


def host_span(span):
    """The traced span (of ``traced_span``) on the host's clock."""
    return span[0] - span[2], span[1] - span[2]


def prompt_bucket(ctx, request):
    """Rows of the prefill program that takes this request's prompt."""
    return min(b for b in ctx["workload"]["server"]["prompt_buckets"]
               if b >= len(request["prompt"]))


def idle_gaps_by_programs(ops, names, n=10):
    """[[what ran before the gap -> what ran after it, seconds summed]]
    over the gaps between the programs' runs on the first chip: the
    program's host code between two device programs is what the host
    was doing (tick bookkeeping between two decodes, admission between
    a decode and a prefill, nothing to do before a long gap)."""
    runs = sorted((start, end, kind) for kind, listed in
                  program_runs(ops, names).items() for start, end in listed)
    total = {}
    for (_, end, before), (start, _, after) in zip(runs, runs[1:]):
        if start > end:
            name = f"host between {before} and {after}"
            total[name] = total.get(name, 0.0) + start - end
    return [[name, seconds] for name, seconds in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]
