"""From a profiler trace to numbers: device-op intervals per chip, their
union (busy), the idle share, a kernel's summed time, the operations
that took longest and the idle gaps by what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes (with
``jax.profiler.ProfileData``, nothing but jax).  Everything else works
on plain ``Op`` tuples, so the arithmetic is tested on the small
recorded trace in ``tests/data`` without a chip.

What a TPU v5e trace looks like (jax 0.9.0, looked at by hand, PR 24):
one plane per chip named ``/device:TPU:<n>``.  Its line ``XLA Modules``
holds one event per program run (``jit_prog(<hash>)``); ``XLA Ops`` one
per executed HLO instruction, named by the instruction's whole text
(``%fusion.378 = (f32[4096]...) fusion(...)``), and nested: a
``%while.13`` covers the events of its body; ``Async XLA Ops`` the
asynchronous copies and collectives from their start to their done
(not loaded: a collective's time is read from the ops line by the
program's own table of its collectives, ``chipbench/report_time.py``).  A
Pallas kernel is a ``custom-call`` whose text has
``custom_call_target="tpu_custom_call"`` and whose instruction is named
after the flax module it sits in (``%attn.21``), not after the kernel.
The benchmark's ``TraceAnnotation``s are on the line ``python3`` of the
plane ``/host:CPU``.  Times are nanoseconds on one clock for all planes.

An instruction is named after the jax primitive it came from, not after
its operation: the gradients' all-reduces are ``%psum.84 = f32[...]
all-reduce(...)``.  So ``load`` keeps of a name the instruction's own,
its operation and its result's type (``psum.84 = all-reduce
f32[32000,4096]``) and marks a Pallas kernel (``attn.21 = custom-call
(...) [tpu_custom_call]``).
"""

import glob
import os
import re
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_LINE = "host"
KERNEL_MARK = " [tpu_custom_call]"
RESULT_CHARS = 72
# operations that only cover the events of their bodies
ENVELOPE = re.compile(r" = (while|conditional|call) ")


class Op(NamedTuple):
    device: int
    line: str
    name: str
    start: float        # seconds
    end: float


def newest_xplane(logdir):
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def short_name(text):
    """``%psum.84 = f32[32000,4096]{1,0:T(8,128)} all-reduce(f32[...] %x),
    channel_id=1, ...`` -> ``psum.84 = all-reduce f32[32000,4096]``: the
    instruction's name, its operation, the type of its result without
    the layouts, and the mark of a Pallas kernel.  A name that is no
    instruction's text stays."""
    name, is_text, rest = text.partition(" = ")
    if not is_text:
        return text
    depth = 0
    for end, char in enumerate(rest):       # the result type ends at the
        depth += char in "({[" or -(char in ")}]")      # first bare space
        if char == " " and depth == 0:
            break
    result = re.sub(r"\{[^{}]*\}", "", rest[:end])
    if len(result) > RESULT_CHARS:
        result = result[:RESULT_CHARS - 3] + "..."
    operation = rest[end + 1:].split("(", 1)[0]
    name = f"{name.lstrip('%')} = {operation} {result}"
    if 'custom_call_target="tpu_custom_call"' in text:
        name += KERNEL_MARK
    return name


def load(logdir, lines=(OPS_LINE, MODULES_LINE),
         host_prefix="chipbench"):
    """Every event of the device planes' ``lines`` as ``Op``s, and the
    benchmark's own annotations on the host's threads (``HOST_LINE``,
    device -1)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(newest_xplane(logdir))
    ops = []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if match and line.name not in lines:
                continue
            for event in line.events:
                if not match and not event.name.startswith(host_prefix):
                    continue
                start = event.start_ns * 1e-9
                ops.append(Op(int(match.group(1)) if match else -1,
                              line.name if match else HOST_LINE,
                              short_name(event.name), start,
                              start + event.duration_ns * 1e-9))
    return ops


def host_spans(ops):
    """[(name, start, end)] of the benchmark's annotations."""
    return [(op.name, op.start, op.end) for op in ops
            if op.line == HOST_LINE]


def describe(logdir, top=12):
    """Planes, lines and the longest-running event names of a trace:
    what to look at by hand before writing a reader against it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(newest_xplane(logdir))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            total, count = {}, 0
            for event in line.events:
                total[event.name] = total.get(event.name, 0) \
                    + event.duration_ns
                count += 1
            longest = sorted(total.items(), key=lambda kv: -kv[1])[:top]
            out.append({"plane": plane.name, "line": line.name,
                        "events": count,
                        "top": [[n, t * 1e-9] for n, t in longest]})
    return out


def device_ops(ops, line=OPS_LINE):
    """{device: [Op] by start} of one line."""
    by_device = {}
    for op in ops:
        if op.line == line:
            by_device.setdefault(op.device, []).append(op)
    for listed in by_device.values():
        listed.sort(key=lambda op: op.start)
    return by_device


def leaf_ops(ops):
    """{device: [Op]} of the ``XLA Ops`` line without the envelopes
    (``while`` ...) that only cover their bodies' events."""
    return {device: [op for op in listed if not ENVELOPE.search(op.name)]
            for device, listed in device_ops(ops).items()}


def merge(intervals):
    """Sorted disjoint [start, end] covering the same points."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def union_seconds(intervals):
    return sum(end - start for start, end in merge(intervals))


def window(ops):
    """(start, end) from the first device op's start to the last one's
    end, over all chips."""
    ops = [op for op in ops if op.line == OPS_LINE]
    return min(op.start for op in ops), max(op.end for op in ops)


def busy_seconds(ops):
    """{device: seconds in which at least one op ran}."""
    return {device: union_seconds([(op.start, op.end) for op in listed])
            for device, listed in device_ops(ops).items()}


def idle_share(ops):
    """{device: 1 - busy / window}; the window is the same for all."""
    start, end = window(ops)
    return {device: 1.0 - busy / (end - start)
            for device, busy in busy_seconds(ops).items()}


def matching_seconds(ops, pattern):
    """{device: summed duration of the ops whose name matches}."""
    pattern = re.compile(pattern)
    out = {}
    for device, listed in leaf_ops(ops).items():
        out[device] = sum(op.end - op.start for op in listed
                          if pattern.search(op.name))
    return out


def top_ops(ops, n=10):
    """[[name, seconds]] of the device ops that took most time, summed
    over chips and divided by their number."""
    by_device = leaf_ops(ops)
    total = {}
    for listed in by_device.values():
        for op in listed:
            total[op.name] = total.get(op.name, 0.0) + op.end - op.start
    chips = max(len(by_device), 1)
    return [[name, t / chips] for name, t in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ops, spans, n=10):
    """[[what the host was doing, seconds]] for the longest gaps of the
    busiest-gapped chip: each gap is named by the benchmark's span
    (name, start, end, on the trace's clock) that covers most of it."""
    by_device = device_ops(ops)
    worst = []
    for listed in by_device.values():
        busy = merge([(op.start, op.end) for op in listed])
        gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])]
        if sum(g[0] for g in gaps) > sum(g[0] for g in worst):
            worst = gaps
    named = []
    for length, start, end in sorted(worst, reverse=True)[:n]:
        best, cover = "no span of the benchmark", 0.0
        for name, s_start, s_end in spans:
            overlap = min(end, s_end) - max(start, s_start)
            if overlap > cover:
                best, cover = name, overlap
        named.append([best, length])
    return named
