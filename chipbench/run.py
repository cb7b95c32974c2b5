#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time.  It finds the cell's configuration, traffic
mix, adapter, reference and metric readers by their names in
BENCHMARK.json (nothing here names one), makes weights and inputs on
the device from the seed, lets the plain float32 reference follow the
first steps of training, drives the program's compiled train step
through those same steps and compares, warms up, measures for
``--seconds``, and prints the result as the last line of its output.
With ``--trace 1`` it then traces a few more steps and reports the
per-layer metrics instead of the end-to-end ones.

Off the chip it fails.  ``--rehearse 1`` is the benchmark's own switch
for the CPU: the configuration's and the mix's ``rehearsal`` sizes,
interpret-mode kernels, virtual devices; it prints no metric.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")
sys.path.insert(0, ROOT)


class Refused(Exception):
    """The run cannot stand as a measurement: no result is printed."""


def say(kind, **fields):
    print(json.dumps({kind: fields}), flush=True)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_reader(directory, name):
    """A metric's reader: the file of its own under ``directory``."""
    path = os.path.join(HERE, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{directory}_{name}".replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(bench, name):
    """(cell, configuration entry) of BENCHMARK.json."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no cell {name!r} in BENCHMARK.json: "
                      f"{sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, entry


def metrics_of(bench, group, cell_name):
    """The metrics of ``group`` that this cell reports."""
    return [m for m in bench[group]
            if cell_name in m.get("workloads", [cell_name])]


def with_rehearsal(data, rehearse):
    data = dict(data)
    sizes = data.pop("rehearsal", {})
    if rehearse:
        data.update(sizes)
    return data


def memory(devices):
    """What the runtime counts on the fullest chip (it counts live
    arrays; a program's temporaries are in the reserved bytes)."""
    stats = max((d.memory_stats() or {} for d in devices),
                key=lambda s: s.get("peak_bytes_in_use") or 0)
    return {k: stats.get(k) for k in (
        "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
        "peak_bytes_reserved", "bytes_limit")}


class ProgramMemory:
    """The program's peak on the fullest chip: its live arrays and the
    bytes the runtime reserves for its programs' temporaries.

    The TPU runtime counts the two apart (``bytes_in_use`` and
    ``bytes_reserved``, which together with the free bytes make
    ``bytes_limit``), and keeps one lifetime maximum of each.  The
    reference ran first in this process, so a lifetime maximum may be
    the reference's: the live peak is taken as the runtime's only if it
    rose after this object was made, and otherwise, like the reserved bytes, as
    the most that ``sample()`` saw in the program's phases."""

    def __init__(self, devices):
        """Make it when the reference is done and the program not yet
        started."""
        self.devices = devices
        self.before = [(d.memory_stats() or {}).get("peak_bytes_in_use") or 0
                       for d in devices]
        self.most = [0] * len(devices)

    def sample(self):
        for i, d in enumerate(self.devices):
            stats = d.memory_stats() or {}
            live = stats.get("bytes_in_use") or 0
            peak = stats.get("peak_bytes_in_use") or 0
            if peak > self.before[i]:
                live = peak
            self.most[i] = max(self.most[i],
                               live + (stats.get("bytes_reserved") or 0))
        return max(self.most)


def device_record(devices, memory_peak_bytes):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(memory_peak_bytes)}


class Shared:
    """What the rank threads of this process share: a barrier, and what
    the lead rank decides for all."""

    def __init__(self, n):
        self.barrier = threading.Barrier(n)
        self.values = {}

    def sync(self):
        self.barrier.wait(timeout=600)

    def guarded(self, fn):
        """``fn`` for every rank; a rank that fails breaks the barrier,
        so that the others fail at once and do not wait for it."""
        def run(*args):
            try:
                return fn(*args)
            except BaseException:
                self.barrier.abort()
                raise
        return run


class Compare:
    """The numbers compared with the reference, each beside its limit.
    A number whose limit is ``None`` (``null`` in the cell's limits
    file) is read and printed and not compared: one with no reading it
    has to stay under could only fail sound runs."""

    def __init__(self):
        self.ok = True
        self.numbers = {}       # name -> [value, limit], as compared
        self.notes = {}         # name -> what was read (a leaf, the losses)

    def check(self, name, value, limit, note=""):
        value = float(value)
        ok = limit is None or (math.isfinite(value) and value <= limit)
        self.ok &= ok
        self.numbers[name] = [value, limit]
        self.notes[name] = note
        return ok

    def lines(self):
        """A line a number, for the end of the errors."""
        return [f"compared {name} {value!r} limit {limit!r}"
                + (f" ({self.notes[name]})" if self.notes[name] else "")
                for name, (value, limit) in self.numbers.items()]


def is_kernel(leaf):
    """A matrix or a filter, as flax names them."""
    return leaf.endswith("['kernel']")


def leaf_gaps(program, reference, chosen=lambda leaf: True):
    """{leaf: the gap between the program's norm of it and the
    reference's, against the reference's norm of that leaf or of the
    tree's median leaf, whichever is larger} over the leaves ``chosen``."""
    ref = {k: float(v) for k, v in reference.items()}
    median = sorted(ref.values())[len(ref) // 2]
    return {k: abs(float(program[k]) - ref[k]) / max(ref[k], median)
            for k in ref if chosen(k)}


def worst_leaf_gap(program, reference, chosen=lambda leaf: True):
    """The widest of ``leaf_gaps``, and the leaf."""
    gaps = leaf_gaps(program, reference, chosen)
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def mean_kernel_gap(program, reference):
    """The mean over the matrices and filters of the gap between the
    program's norm of a leaf and the reference's, against the
    reference's."""
    kernels = [k for k in reference if is_kernel(k)]
    return sum(abs(float(program[k]) - float(reference[k]))
               / float(reference[k]) for k in kernels) / len(kernels), \
        f"{len(kernels)} kernels"


def mean_other_gap(program, reference):
    """The mean of ``leaf_gaps`` over the leaves that are no matrix or
    filter (scales, biases): steady from seed to seed where the worst
    of them is one short vector's norm, which swings."""
    gaps = leaf_gaps(program, reference, lambda leaf: not is_kernel(leaf))
    return sum(gaps.values()) / len(gaps), f"{len(gaps)} other leaves"


# how a tree of leaf norms is compared; ``limits/<cell>.json`` names one
# or more of these with a limit each, and says why
NORM_GAPS = {
    "worst_leaf": worst_leaf_gap,
    "worst_kernel": lambda p, r: worst_leaf_gap(p, r, is_kernel),
    "worst_other": lambda p, r: worst_leaf_gap(
        p, r, lambda leaf: not is_kernel(leaf)),
    "mean_kernel": mean_kernel_gap,
    "mean_other": mean_other_gap,
}


def run_window(step, state, batch, n_steps, per_reading, in_flight, spans):
    """``n_steps`` steps, up to ``in_flight`` of them enqueued ahead of
    the one whose result the host waits for (a training loop that logs
    its loss a few steps late); the host's clock at the first enqueue
    and at the result of each reading's last step."""
    import jax

    losses, marks = [], []
    clock = time.perf_counter

    def await_result(j):
        jax.block_until_ready(losses[j])
        if (j + 1) % per_reading == 0:
            marks.append(clock())

    first = clock()
    for i in range(n_steps):
        t0 = clock()
        state, loss = step(state, batch)
        spans.append(clock() - t0)
        losses.append(loss)
        if i >= in_flight:
            await_result(i - in_flight)
    for j in range(max(n_steps - in_flight, 0), n_steps):
        await_result(j)
    return state, losses, first, marks


class Cell:
    """One cell of BENCHMARK.json with everything a run of it needs,
    each piece found by its name; starts jax."""

    def __init__(self, name, rehearse, need_chips=True):
        self.rehearse = rehearse = bool(rehearse)
        self.bench = bench = load_json("BENCHMARK.json")
        self.cell, entry = find_cell(bench, name)
        self.config = with_rehearsal(load_json(entry["file"]), rehearse)
        self.workload = with_rehearsal(
            load_json(bench["paths"][0], "workloads",
                      self.cell["traffic"] + ".json"), rehearse)
        self.chips, self.ranks = self.cell["chips"], self.workload["ranks"]
        if rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={self.chips}")

        import jax

        if rehearse:
            jax.config.update("jax_num_cpu_devices", self.chips)
            self.cache_dir = None
        else:
            from horovod_tpu.utils.compile_cache import place_compile_cache

            self.cache_dir = place_compile_cache()
        # the machine's part of start-up, read apart and left out of
        # set-up: the TPU runtime's start takes 6 to 12 s and drifts by
        # seconds from one process to the next (PERF.md section 2)
        self.t_runtime = time.perf_counter()
        self.devices = devices = jax.devices()
        self.runtime_start_seconds = time.perf_counter() - self.t_runtime
        if not rehearse and devices[0].platform != "tpu":
            raise Refused(f"platform is {devices[0].platform!r}, not 'tpu'")
        if need_chips and len(devices) < self.chips:
            raise Refused(
                f"{len(devices)} devices, the cell needs {self.chips}")

        from chipbench import flops, weights

        self.peaks = None if rehearse \
            else flops.peaks(devices[0].device_kind)
        self.adapter = importlib.import_module(
            f"chipbench.adapters.{self.config['adapter']}")
        self.reference = importlib.import_module(
            f"chipbench.references.{self.config['adapter']}")
        self.make_input = importlib.import_module(
            f"chipbench.inputs.{self.workload['input']['kind']}").make
        limits = load_json(bench["paths"][0], "limits", name + ".json")
        self.limits = limits["rehearsal" if rehearse else "limits"]
        self.control = limits.get("control", "fp8")

        # the reference's tree has to be the program's, shape for shape
        self.spec = self.reference.param_spec(self.config)
        self.aux_spec = self.reference.aux_spec(self.config)
        params, aux = self.adapter.param_shapes(self.config, self.workload)
        if weights.shapes(self.spec) != weights.shapes(params) or (
                self.aux_spec is not None
                and weights.shapes(self.aux_spec) != weights.shapes(aux)):
            raise Refused(
                "the reference's parameter tree is not the program's")
        self.rows = self.ranks * self.workload["batch"]
        self.make_weights = jax.jit(lambda k: (
            weights.make(k, self.spec),
            None if self.aux_spec is None
            else weights.make(k, self.aux_spec)))
        self.make_batch = jax.jit(lambda k: self.make_input(
            jax.random.fold_in(k, 1), self.config, self.workload, self.rows))

    def follow_reference(self, key, batch, mode="float32"):
        """The numbers of the reference's first steps, on the host."""
        return self.reference.follow(self.config, self.workload, key, batch,
                                     self.workload["check_steps"], mode)

    def first_steps(self, step, state, batch, key, lead, shared):
        """The program's first steps through the window's own call and
        feed; (state, the numbers the reference is compared on).  Where
        the mix asks for the loss after the last step the reference
        follows (``check_loss_after``), one more step is driven for its
        loss, which says where that update led."""
        import jax

        from chipbench import weights

        followed = self.workload["check_steps"]
        losses, grad_norms, delta_norms = [], None, None
        for i in range(followed
                       + bool(self.workload.get("check_loss_after"))):
            state, loss = step(state, batch)
            losses.append(float(loss))
            if lead and i == 0:
                grad_norms = jax.device_get(jax.jit(
                    lambda s: weights.leaf_norms(
                        self.adapter.first_gradient(s, self.workload))
                )(state))
            if lead and i == followed - 1:
                delta_norms = jax.device_get(jax.jit(
                    lambda p, k: weights.leaf_norms(jax.tree.map(
                        lambda a, b: a - b, p, weights.make(k, self.spec)))
                )(state["params"], key))
            shared.sync()
        return state, {"losses": losses, "grad_norms": grad_norms,
                       "delta_norms": delta_norms}

    def start(self, key, batch, rank, lead):
        """(step, state, this rank's staged batch) of the program."""
        import jax

        adapter, n_ranks = self.adapter, self.ranks
        step = adapter.make_step(self.config, self.workload, self.rehearse)
        params, aux = self.make_weights(key) if lead else (None, None)
        state = adapter.init_state(step, params, aux)
        del params, aux
        mine = jax.tree.map(
            lambda a: a.reshape((n_ranks, -1) + a.shape[1:])[rank], batch)
        staged = step.place_batch(mine) if n_ranks == 1 \
            else jax.device_get(mine)
        return step, state, staged


def gaps(program, ref, limits):
    """[(name, value, limit, note)] of every number compared with the
    reference.  Each step's loss has a limit of its own (the first
    hardly moves with precision and is held against a part of the batch
    left out; later ones carry the optimizer's steps; ``None`` where
    the cell reads a step's loss and does not compare it).  A tree of leaf
    norms has one limit, which is for its worst leaf, or one for each
    way of ``NORM_GAPS`` that the cell compares it in."""
    out = [(f"loss_step{i + 1}_abs_gap", abs(got - want), limit,
            f"program {got:.6f} reference {want:.6f}")
           for i, (got, want, limit) in enumerate(zip(
               program["losses"], ref["losses"], limits["loss_abs_gap"],
               strict=True))]
    for name, numbers, key in (
            ("first_gradient_norm", "grad_norms", "grad_norm_gap"),
            ("parameter_change_norm", "delta_norms", "delta_norm_gap")):
        ways = limits[key] if isinstance(limits[key], dict) \
            else {"worst_leaf": limits[key]}
        for way, limit in ways.items():
            gap, note = NORM_GAPS[way](program[numbers], ref[numbers])
            out.append((f"{name}_{way}_gap", gap, limit, note))
    return out


def run(args):
    import jax

    c = Cell(args.workload, args.rehearse)
    rehearse, bench, cell = c.rehearse, c.bench, c.cell
    config, workload, adapter = c.config, c.workload, c.adapter
    chips, ranks, devices = c.chips, c.ranks, c.devices

    from chipbench import trace_reduce, weights

    end_to_end = [(m, load_reader("end_to_end", m["name"]))
                  for m in metrics_of(bench, "end_to_end", cell["name"])]
    per_layer = [(m, load_reader("layer_metrics", m["name"]))
                 for m in metrics_of(bench, "per_layer", cell["name"])]
    counter_names = sorted({n for _, r in per_layer
                            for n in getattr(r, "COUNTERS", [])})
    say("run", cell=cell["name"], config=cell["config"],
        traffic=cell["traffic"], seed=args.seed, seconds=args.seconds,
        trace=args.trace, rehearsal=rehearse, compile_cache_dir=c.cache_dir,
        device={"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices)})
    key = weights.seed_key(args.seed)
    global_batch = c.make_batch(key)

    # the reference first, alone on the chip; its time is not set-up
    t_reference = time.perf_counter()
    ref = c.follow_reference(key, global_batch)
    reference_seconds = time.perf_counter() - t_reference
    say("reference", seconds=reference_seconds, losses=ref["losses"],
        memory=memory(devices))

    from horovod_tpu import telemetry

    def counters():
        return {n: telemetry.counter_total(n) for n in counter_names}

    shared = Shared(ranks)
    compare = Compare()
    program_memory = ProgramMemory(devices)

    def drive(rank, n_ranks):
        lead = rank == 0
        t_drive = time.perf_counter()
        step, state, batch = c.start(key, global_batch, rank, lead)
        t_started = time.perf_counter()
        state, found = c.first_steps(step, state, batch, key, lead, shared)
        t_checked = time.perf_counter()
        if lead:
            for name, value, limit, note in gaps(found, ref, c.limits):
                compare.check(name, value, limit, note)
            compare.check(
                "first_loss_abs_gap_to_uniform",
                abs(found["losses"][0] - c.reference.first_loss(config)),
                c.limits["first_loss_abs_gap"])

        # warm-up, which also says how many steps fill --seconds
        t0 = time.perf_counter()
        for _ in range(workload["warmup_steps"]):
            state, loss = step(state, batch)
        jax.block_until_ready(loss)
        if lead:
            each = (time.perf_counter() - t0) / workload["warmup_steps"]
            per_reading = workload["steps_per_reading"]
            readings = max(1, round(args.seconds / each / per_reading))
            now = time.perf_counter()
            shared.values.update(
                n_steps=readings * per_reading,
                counters_start=counters(),
                setup_seconds=now - T_START - reference_seconds
                - c.runtime_start_seconds)
            say("setup", seconds=shared.values["setup_seconds"], phases={
                "imports": c.t_runtime - T_START,
                "device_runtime_start_not_counted": c.runtime_start_seconds,
                "adapter_weights_inputs": t_reference - c.t_runtime
                - c.runtime_start_seconds,
                "reference_not_counted": reference_seconds,
                "hvd_start": t_drive - t_reference - reference_seconds,
                "step_state_batch": t_started - t_drive,
                "first_steps_and_check": t_checked - t_started,
                "warmup": now - t0}, warm_step_seconds=each,
                memory=memory(devices))
            program_memory.sample()
        shared.sync()

        spans = []
        state, window_losses, first, marks = run_window(
            step, state, batch, shared.values["n_steps"],
            workload["steps_per_reading"], workload["steps_in_flight"], spans)
        shared.sync()
        if not lead:
            window_losses = None
        result = {"spans": spans, "first": first, "marks": marks,
                  "losses": window_losses, "counters_end": counters()}

        if args.trace:
            if lead:
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(TRACE_DIR,
                                         profiler_options=options)
            shared.sync()
            for i in range(workload["trace_steps"]):
                with jax.profiler.TraceAnnotation(
                        f"chipbench: rank {rank} enqueues a step"):
                    state, loss = step(state, batch)
            with jax.profiler.TraceAnnotation(
                    f"chipbench: rank {rank} waits for the last step"):
                jax.block_until_ready(loss)
            shared.sync()
            if lead:
                jax.profiler.stop_trace()
        if lead:
            result["replicas"] = adapter.replicas_agree(state) \
                if hasattr(adapter, "replicas_agree") and n_ranks > 1 \
                else None
            result["device"] = device_record(devices,
                                             program_memory.sample())
            say("memory_after_window", **memory(devices))
        return result

    result = adapter.launch(workload, shared.guarded(drive))[0]

    # ---- what the window showed
    n_steps = shared.values["n_steps"]
    losses = [float(v) for v in result["losses"]]
    marks = [result["first"]] + result["marks"]
    failed = sum(not math.isfinite(v) for v in losses)
    readings = [b - a for a, b in zip(marks, marks[1:])]
    say("window", steps=n_steps, seconds=marks[-1] - marks[0],
        readings=len(readings), first_loss=losses[0], last_loss=losses[-1],
        longest_reading={"seconds": max(readings),
                         "index": readings.index(max(readings))},
        longest_dispatch={"seconds": max(result["spans"]),
                          "index": result["spans"].index(
                              max(result["spans"]))})
    compare.check("window_steps_with_a_loss_not_finite", failed, 0)
    misses = result["counters_end"].get(
        "horovod_program_cache_misses_total")
    if misses is not None:
        compare.check(
            "window_program_cache_misses", misses
            - shared.values["counters_start"][
                "horovod_program_cache_misses_total"], 0)
    if result["replicas"] is not None:
        compare.check("leaves_that_differ_between_chips",
                      result["replicas"], 0)

    ctx = {
        "cell": cell, "config": config, "workload": workload,
        "adapter": adapter, "chips": chips, "ranks": ranks, "peaks": c.peaks,
        "flops_per_sample": adapter.flops_per_sample(config, workload),
        "setup_seconds": shared.values["setup_seconds"],
        "window": {
            "steps": n_steps,
            "samples_per_step": c.rows * workload["samples_per_row"],
            "seconds": marks[-1] - marks[0],
            "samples_per_second": n_steps * c.rows
            * workload["samples_per_row"] / (marks[-1] - marks[0]),
            "steps_per_reading": workload["steps_per_reading"],
            "reading_seconds": readings,
        },
        "spans": {"step_dispatch": result["spans"]},
        "counters": {"window_start": shared.values["counters_start"],
                     "window_end": result["counters_end"]},
        "trace": None, "trace_steps": workload["trace_steps"],
    }
    device = result["device"]
    breakdown = None
    if args.trace:
        if rehearse:
            say("trace", planes=[f"{d['plane']} / {d['line']}" for d in
                                 trace_reduce.describe(TRACE_DIR, top=0)])
        else:
            ops = trace_reduce.load(TRACE_DIR)
            ctx["trace"] = ops
            busy = trace_reduce.busy_seconds(ops)
            start, end = trace_reduce.window(ops)
            device["busy_s"] = sum(busy.values()) / len(busy)
            device["window_s"] = end - start
            breakdown = {
                "device_ops": trace_reduce.top_ops(ops),
                "idle_gaps": trace_reduce.idle_gaps(
                    ops, trace_reduce.host_spans(ops))}
        if not args.keep_trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)

    metrics = {}
    if not rehearse:
        for metric, reader in (per_layer if args.trace else end_to_end):
            value = reader.read(ctx)
            if value is not None:
                metrics[metric["name"]] = {"value": float(value),
                                           "unit": metric["unit"]}
    line = {"correct": bool(compare.ok), "attempted": n_steps,
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    if rehearse:
        line["rehearsal"] = True
    # every number compared beside its limit: the last lines of the
    # errors and the last key of the result
    print("\n".join(compare.lines()), file=sys.stderr)
    line["compared"] = compare.numbers
    return line


def flow(name):
    """The run of this cell's kind: ``run`` above, the training flow, or
    the ``run`` of the file of chipbench/ that the configuration's
    adapter names as its ``FLOW`` (``serve_run``: a served run)."""
    _, entry = find_cell(load_json("BENCHMARK.json"), name)
    adapter = importlib.import_module(
        "chipbench.adapters." + load_json(entry["file"])["adapter"])
    if not hasattr(adapter, "FLOW"):
        return run
    # that file imports this one: under either name it is this module
    sys.modules.setdefault("chipbench.run", sys.modules[__name__])
    return importlib.import_module("chipbench." + adapter.FLOW).run


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    parser.add_argument("--keep-trace", type=int, choices=(0, 1), default=0,
                        help="leave the profiler's files in "
                             ".chipbench_trace/ for tools/trace_dump.py")
    args = parser.parse_args(argv)
    try:
        line = flow(args.workload)(args)
    except Refused as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
