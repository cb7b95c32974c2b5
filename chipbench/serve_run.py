"""One run of a SERVED cell of BENCHMARK.json: ``run.py`` hands over to
``run`` here where the configuration's adapter names this file as its
``FLOW``.  The request is the unit of work, not the step.

In order: the cell, the compile cache, the platform, the reference's
tree against the program's; weights from the seed in the served type;
the server (its programs warmed up, its tick thread running); the
**check**: a few fixed requests through that same server, their cached
keys and values read back by the journal's block lists; warm-up traffic;
the **window**: an open-loop generator submits each request at its
scheduled time whether or not earlier ones have finished, every token is
stamped with the host's clock as the server hands it out; with
``--trace 1`` a traced span of the same traffic; the drain; the memory's
peak; the program's state freed; then the plain float32 reference over
the check's requests and a sample of the window's own, and the
comparison.  The result line has the training flow's format;
``attempted`` and ``failed`` count requests.
"""

import functools
import gc
import json
import os
import queue
import shutil
import statistics
import sys
import tempfile
import threading
import time

import numpy as np

from chipbench import run as harness
from chipbench.run import Refused, say

clock = time.perf_counter
TRACED_SPAN = "chipbench: traced span"


class Load:
    """Open-loop load on a server from one generator thread, which keeps
    the schedule, and one submitter thread, which makes the calls in
    the schedule's order (a call can block on the server's lock, and the
    schedule must not wait for it).  Every request keeps its own record:
    ``due`` (scheduled), ``sent`` (handed over: the generator's
    lateness is ``sent - due``), ``submit`` (start and end of the call),
    ``tokens`` and ``stamps`` (the host's clock at each token), ``done``,
    ``refused``."""

    def __init__(self, server):
        self.server = server
        self.handed = queue.SimpleQueue()
        self.requests = []
        self.threads = []

    def _on_token(self, request):
        tokens, stamps, server = request["tokens"], request["stamps"], \
            self.server

        def on_token(token):
            now = clock()
            if token is None:
                request["done"] = now
                return
            tokens.append(token)
            stamps.append(now)
            if len(stamps) == 1:
                request["blocks_in_use"] = server.blocks_in_use()
        return on_token

    def submit(self, request):
        request.update(tokens=[], stamps=[], done=None, refused=None)
        request["submit"] = [clock(), None]
        try:
            request["handle"] = self.server.submit(
                request["prompt"], request["budget"],
                self._on_token(request))
        except Exception as exc:        # refused: counted, never hidden
            request["refused"] = repr(exc)
        request["submit"][1] = clock()
        self.requests.append(request)

    def start(self, requests, t0):
        """Submit ``requests`` at ``t0 + at`` each, from now on."""
        def generate():
            for request in requests:
                request["due"] = t0 + request["at"]
                while True:
                    wait = request["due"] - clock()
                    if wait <= 0:
                        break
                    time.sleep(wait)
                request["sent"] = clock()
                self.handed.put(request)
            self.handed.put(None)

        def hand_over():
            while True:
                request = self.handed.get()
                if request is None:
                    return
                self.submit(request)

        self.threads = [
            threading.Thread(target=generate, name="chipbench-generator",
                             daemon=True),
            threading.Thread(target=hand_over, name="chipbench-submitter",
                             daemon=True)]
        for thread in self.threads:
            thread.start()

    def offer(self, requests, warmup_seconds):
        """Start the schedule a moment from now; when its window opens
        (the warm-up traffic over) on the host's clock."""
        t_start = clock() + 0.05
        self.start(requests, t_start)
        return t_start + warmup_seconds

    def finish(self, seconds):
        """Wait for the schedule's end, then up to ``seconds`` for every
        request's last token."""
        for thread in self.threads:
            thread.join()
        deadline = clock() + seconds
        for request in self.requests:
            if request["refused"] is None:
                request["handle"].wait(max(deadline - clock(), 0.0))


def is_failed(request):
    return request["refused"] is not None or request["done"] is None \
        or len(request["tokens"]) != request["budget"]


def journal_blocks(path):
    """{sequence id: its block list} of a slot journal's admissions."""
    blocks = {}
    with open(path, encoding="utf-8") as f:
        for line in f:
            try:
                record = json.loads(line)
            except ValueError:
                continue
            if record.get("e") == "admit":
                blocks[record["seq"]] = record["blocks"]
    return blocks


@functools.lru_cache(maxsize=None)
def _take_blocks():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda pool, ids: jnp.take(pool, ids, axis=1))


def read_cache_rows(server, blocks, rows):
    """A sequence's cached keys and values, (layers, rows, KV, D) each
    on the host in the pools' type, gathered by its block list (padded
    to its table bucket: a program a bucket, not a length)."""
    width = server.programs.table_bucket(len(blocks))
    ids = np.zeros(width, np.int32)
    ids[:len(blocks)] = blocks
    out = []
    for pool in server.pools():
        got = np.asarray(_take_blocks()(pool, ids))
        out.append(got.reshape((got.shape[0], -1) + got.shape[3:])[:, :rows])
    return out


def sequence_of(request):
    """What the reference reads for a request: the prompt and every
    emitted token but the last (which was never fed), and the positions
    whose logits chose the emitted tokens."""
    prompt, tokens = request["prompt"], request["tokens"]
    ids = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(tokens[:-1], np.int32)])
    first = len(prompt) - 1
    return {"tokens": ids, "read": np.arange(first, first + len(tokens))}


def token_margins(logits, tokens):
    """For each emitted token, the reference's largest logit at that
    position less its logit of the token the program chose (0 where
    they agree): teacher-forced on the program's own stream, so a
    flipped near-tie does not cascade."""
    logits = np.asarray(logits, np.float64)
    chosen = logits[np.arange(len(tokens)), np.asarray(tokens)]
    return logits.max(axis=-1) - chosen


def cache_gaps(found, reference, prompt_rows):
    """Squared norms by layer of (program - reference) and of the
    reference, over the prompt's rows (which prefill and ingest wrote)
    and over the generated rows (which decode wrote), and the widest
    relative gap of one row of one layer."""
    sums = {}
    worst = 0.0
    for name in ("k", "v"):
        got = found[name].astype(np.float32)
        want = reference[name]
        diff = ((got - want) ** 2).sum(axis=(2, 3))        # (layers, rows)
        size = (want ** 2).sum(axis=(2, 3))
        worst = max(worst, float(np.sqrt((diff / size).max())))
        for rows, part in (("prefill", slice(0, prompt_rows)),
                           ("decode", slice(prompt_rows, None))):
            sums[name, rows] = (diff[:, part].sum(axis=1),
                                size[:, part].sum(axis=1))
    return sums, worst


def compare_with_reference(compare, limits, checked, sampled, outputs):
    """Every number of the comparison, each beside its limit.  The
    check's requests are compared on their cache and their tokens, the
    window's sample on its tokens alone (its cache was never read)."""
    totals, worst_row = {}, 0.0
    margins = {"check": [], "window": []}
    for request, ref in zip(checked + sampled, outputs):
        kind = "check" if "cache" in request else "window"
        margins[kind].append(token_margins(ref["logits"],
                                           request["tokens"]))
        if kind == "window":
            continue
        sums, worst = cache_gaps(request["cache"], ref,
                                 len(request["prompt"]))
        worst_row = max(worst_row, worst)
        for key, (diff, size) in sums.items():
            have = totals.setdefault(key, [0.0, 0.0])
            have[0], have[1] = have[0] + diff, have[1] + size
    if totals:
        for rows in ("prefill", "decode"):
            gaps = {(name, layer): float(gap) for name in ("k", "v")
                    for layer, gap in enumerate(np.sqrt(
                        totals[name, rows][0] / totals[name, rows][1]))}
            name, layer = max(gaps, key=gaps.get)
            compare.check(f"check_cache_{rows}_rows_worst_layer_gap",
                          gaps[name, layer],
                          limits[f"check_cache_{rows}_rows_worst_layer_gap"],
                          f"{name} of layer {layer}")
        compare.check("check_cache_worst_row_gap", worst_row,
                      limits["check_cache_worst_row_gap"])
    for kind, found in margins.items():
        if not found:
            continue
        found = np.concatenate(found)
        compare.check(f"{kind}_token_margin_worst", found.max(),
                      limits[f"{kind}_token_margin_worst"],
                      f"{len(found)} tokens, {int((found > 0).sum())} "
                      f"not the reference's first")
        compare.check(f"{kind}_token_margin_mean", found.mean(),
                      limits[f"{kind}_token_margin_mean"])


def window_sample(requests, span, count, seed):
    """``count`` of the requests the window finished, the longest among
    them, the rest drawn from the seed."""
    finished = [r for r in requests if not is_failed(r)
                and span[0] <= r["done"] < span[1]]
    if not finished:
        return []
    longest = max(finished,
                  key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 11])
    picked = rng.permutation(len(rest))[:max(count - 1, 0)]
    return [longest] + [rest[i] for i in sorted(picked)]


def window_record(config, flops, requests, span):
    """The window in the shape the end-to-end readers know: the tokens
    it processed (the prompts it prefilled and the generated tokens it
    fed back), the model's FLOPs of exactly those, and every gap
    between a stream's consecutive tokens that closed inside it."""
    start, end = span
    tokens = work = emitted = 0
    gaps = []
    for r in requests:
        stamps, prompt = r["stamps"], len(r["prompt"])
        if stamps and start <= stamps[0] < end:
            tokens += prompt
            work += flops.prefill_flops(config, prompt)
        for i, stamp in enumerate(stamps):
            if not start <= stamp < end:
                continue
            emitted += 1
            if i:
                tokens += 1
                work += flops.decode_flops(config, prompt + i - 1)
                gaps.append(stamp - stamps[i - 1])
    return {"tokens": tokens, "flops": work, "emitted": emitted,
            "gaps": gaps}


def percentile(values, q):
    """The q-th of 100 (inclusive method), or the one value."""
    if len(values) < 2:
        return values[0]
    return statistics.quantiles(values, n=100, method="inclusive")[q - 1]


class Served:
    """One served cell of BENCHMARK.json with everything a run of it
    needs, each piece found by its name; starts jax."""

    def __init__(self, name, rehearse, changes=None):
        self.rehearse = rehearse = bool(rehearse)
        self.bench = bench = harness.load_json("BENCHMARK.json")
        self.cell, entry = harness.find_cell(bench, name)
        home = bench["paths"][0]
        self.config = dict(harness.with_rehearsal(
            harness.load_json(entry["file"]), rehearse), **(changes or {}))
        self.workload = harness.with_rehearsal(harness.load_json(
            home, "workloads", self.cell["traffic"] + ".json"), rehearse)
        self.limits = harness.load_json(
            home, "limits", name + ".json")[
                "rehearsal" if rehearse else "limits"]
        self.chips = self.cell["chips"]
        if rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"

        import importlib

        import jax

        if rehearse:
            self.cache_dir = None
        else:
            from horovod_tpu.utils.compile_cache import place_compile_cache

            self.cache_dir = place_compile_cache()
        self.t_runtime = clock()
        self.devices = devices = jax.devices()[:self.chips]
        self.runtime_start_seconds = clock() - self.t_runtime
        if not rehearse and devices[0].platform != "tpu":
            raise Refused(f"platform is {devices[0].platform!r}, not 'tpu'")
        if len(devices) < self.chips:
            raise Refused(
                f"{len(devices)} devices, the cell needs {self.chips}")

        from chipbench import flops, served_weights, weights

        config, workload = self.config, self.workload
        self.peaks = None if rehearse \
            else flops.peaks(devices[0].device_kind)
        self.adapter = importlib.import_module(
            f"chipbench.adapters.{config['adapter']}")
        self.reference = reference = importlib.import_module(
            f"chipbench.references.{config['adapter']}")
        self.flops = importlib.import_module(
            f"chipbench.{workload['flops']}")
        self.inputs = importlib.import_module(
            f"chipbench.inputs.{workload['input']['kind']}")
        if weights.shapes(reference.param_spec(config)) != weights.shapes(
                self.adapter.param_shapes(config, workload)):
            raise Refused(
                "the reference's parameter tree is not the program's")
        self.make_params = jax.jit(lambda k: served_weights.make_stacked(
            k, reference.top_spec(config), reference.layer_spec(config),
            config["num_hidden_layers"], served_weights.dtype_of(config)))
        self.scratch = tempfile.mkdtemp(prefix="chipbench_serve_")

    def serve(self, key):
        """(server, its journal's path): weights of ``key`` in the
        served type, the programs warmed up, the tick thread running."""
        import jax

        params = jax.block_until_ready(self.make_params(key))
        journal = tempfile.mktemp(prefix="journal_", suffix=".jsonl",
                                  dir=self.scratch)
        return self.adapter.Server(self.config, self.workload, params,
                                   journal), journal

    def check(self, load, journal, seed):
        """The check's fixed requests through the server, submitted
        together so that they decode side by side in mixed slots, and
        each one's cached keys and values read back."""
        checked = self.inputs.fixed(seed, self.config,
                                    self.workload["check_requests"])
        for request in checked:
            request["due"] = request["sent"] = clock()
            load.submit(request)
        load.finish(self.workload["drain_seconds"])
        blocks = journal_blocks(journal)
        for request in checked:
            if not is_failed(request):
                rows = len(request["prompt"]) + len(request["tokens"]) - 1
                k, v = read_cache_rows(
                    load.server, blocks[request["handle"].seq_id], rows)
                request["cache"] = {"k": k, "v": v}
        return checked

    def traffic(self, seed, seconds, traced, rate=None):
        """(requests, seconds of the traced span) of one run's schedule:
        warm-up, window and, traced, the lead and the traced span."""
        workload = self.workload
        trace_seconds = workload["trace_seconds"] if traced else 0.0
        lead = workload["trace_lead_seconds"] if traced else 0.0
        return self.inputs.make(
            seed, self.config, workload["traffic"],
            [("warmup", workload["warmup_seconds"]), ("window", seconds),
             ("trace", lead + trace_seconds)], rate), trace_seconds

    def close(self):
        shutil.rmtree(self.scratch, ignore_errors=True)


def follow_reference(served, key, checked, sampled, mode="float32"):
    """The reference's outputs for the check's requests that came back
    whole and for the window's sample, in that order."""
    whole = [r for r in checked if "cache" in r]
    return whole, served.reference.forward(
        served.config, key, [sequence_of(r) for r in whole + sampled], mode)


def run(args):
    import jax

    c = Served(args.workload, args.rehearse)
    rehearse, bench, cell = c.rehearse, c.bench, c.cell
    config, workload, adapter = c.config, c.workload, c.adapter
    devices, limits = c.devices, c.limits

    from chipbench import trace_reduce, weights

    end_to_end = [(m, harness.load_reader("end_to_end", m["name"]))
                  for m in harness.metrics_of(bench, "end_to_end",
                                              cell["name"])]
    per_layer = [(m, harness.load_reader("layer_metrics", m["name"]))
                 for m in harness.metrics_of(bench, "per_layer",
                                             cell["name"])]
    counter_names = sorted(
        {"horovod_program_cache_misses_total"}
        | {n for _, r in per_layer for n in getattr(r, "COUNTERS", [])})
    say("run", cell=cell["name"], config=cell["config"],
        traffic=cell["traffic"], seed=args.seed, seconds=args.seconds,
        trace=args.trace, rehearsal=rehearse, compile_cache_dir=c.cache_dir,
        device={"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices)})

    from horovod_tpu import telemetry

    def counters():
        return {n: telemetry.counter_total(n) for n in counter_names}

    # ---- weights, the server, the check
    key = weights.seed_key(args.seed)
    program_memory = harness.ProgramMemory(devices)
    t0 = clock()
    server, journal = c.serve(key)
    t_served = clock()
    say("server", programs_warmed=server.warmed, seconds=t_served - t0,
        memory=harness.memory(devices))
    load = Load(server)
    checked = c.check(load, journal, args.seed)
    t_checked = clock()
    say("check", requests=len(checked), seconds=t_checked - t_served,
        failed=sum(map(is_failed, checked)))

    # ---- warm-up traffic, the window, the traced span
    requests, trace_seconds = c.traffic(args.seed, args.seconds, args.trace)
    # what set-up left on the heap is never looked through again: a
    # full collection inside the window stops every thread for as long
    # as the heap is large, the generator among them
    gc.collect()
    gc.freeze()
    t_open = load.offer(requests, workload["warmup_seconds"])
    time.sleep(max(t_open - clock(), 0))
    opened = {"counters": counters(), "cache": adapter.cache_stats(),
              "at": clock()}
    setup_seconds = opened["at"] - harness.T_START \
        - c.runtime_start_seconds
    phases = {
        "imports_and_cell": c.t_runtime - harness.T_START,
        "device_runtime_start_not_counted": c.runtime_start_seconds,
        "adapter_and_readers": t0 - c.t_runtime - c.runtime_start_seconds,
        "weights_and_programs_warmup": t_served - t0,
        "check": t_checked - t_served,
        "warmup_traffic": opened["at"] - t_checked}
    say("setup", seconds=setup_seconds, phases=phases,
        memory=harness.memory(devices))
    time.sleep(max(t_open + args.seconds - clock(), 0))
    closed = {"counters": counters(), "cache": adapter.cache_stats(),
              "at": clock()}
    spans = {"window": (opened["at"], closed["at"]), "trace": None}
    if args.trace:
        shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(harness.TRACE_DIR, profiler_options=options)
        with jax.profiler.TraceAnnotation(TRACED_SPAN):
            begun = clock()
            time.sleep(trace_seconds)
            ended = clock()
        jax.profiler.stop_trace()
        spans["trace"] = (begun, ended)
    load.finish(workload["drain_seconds"])
    ended = clock()
    everyone = checked + requests
    memory_peak = program_memory.sample()
    say("memory_after_window", **harness.memory(devices))

    # ---- the program's part is over: stop it, free it
    sampled = window_sample(requests, spans["window"],
                            workload["window_check_requests"], args.seed)
    kv_leaked = server.blocks_in_use()
    server.stop()
    del server, load.server
    gc.collect()
    c.close()

    compare = harness.Compare()
    failed = sum(map(is_failed, everyone))
    lateness = [r["sent"] - r["due"] for r in requests if "sent" in r]
    vocab = config["vocab_size"]
    record = window_record(config, c.flops, everyone, spans["window"])
    seconds = spans["window"][1] - spans["window"][0]
    waits = [r["stamps"][0] - r["due"] for r in requests if r["stamps"]
             and spans["window"][0] <= r["due"] < spans["window"][1]]
    say("window", seconds=seconds, requests_arrived=sum(
        spans["window"][0] <= r["due"] < spans["window"][1]
        for r in requests), tokens_processed=record["tokens"],
        tokens_emitted=record["emitted"], gaps=len(record["gaps"]),
        lateness_ms={"p99": 1e3 * percentile(lateness, 99),
                     "worst": 1e3 * max(lateness)},
        gap_ms={q: 1e3 * percentile(record["gaps"], q)
                for q in (50, 90, 99)} if record["gaps"] else None,
        ttft_ms={q: 1e3 * percentile(waits, q) for q in (50, 90)}
        if waits else None,
        queued_at_close=sum(
            r["due"] < spans["window"][1]
            and (not r["stamps"] or r["stamps"][0] >= spans["window"][1])
            for r in requests))

    # ---- the reference, alone on the chip; its time is not set-up
    t_reference = clock()
    whole, outputs = follow_reference(c, key, checked, sampled)
    say("reference", seconds=clock() - t_reference,
        sequences=len(outputs),
        tokens=sum(len(r["prompt"]) + len(r["tokens"]) - 1
                   for r in whole + sampled))
    compare_with_reference(compare, limits, whole, sampled, outputs)
    compare.check("check_requests_compared", len(checked) - len(whole), 0)
    compare.check("window_requests_compared",
                  workload["window_check_requests"] - len(sampled), 0)
    compare.check("requests_failed", failed, 0,
                  f"{len(everyone)} sent")
    compare.check("tokens_outside_the_vocabulary", sum(
        not 0 <= t < vocab for r in everyone for t in r["tokens"]), 0)
    compare.check("window_program_cache_misses",
                  closed["cache"][1] - opened["cache"][1], 0)
    compare.check("cache_blocks_leaked", kv_leaked, 0)
    compare.check("generator_lateness_worst_ms", 1e3 * max(lateness),
                  limits["generator_lateness_worst_ms"])

    ctx = {
        "cell": cell, "config": config, "workload": workload,
        "adapter": adapter, "chips": c.chips, "ranks": 1, "peaks": c.peaks,
        "flops_per_sample": record["flops"] / max(record["tokens"], 1),
        "setup_seconds": setup_seconds,
        "window": {
            "steps": record["emitted"], "samples_per_step": 1,
            "seconds": seconds,
            "samples_per_second": record["tokens"] / seconds,
            "steps_per_reading": 1, "reading_seconds": record["gaps"],
        },
        "spans": {"step_dispatch": [
            r["submit"][1] - r["submit"][0] for r in requests
            if spans["window"][0] <= r["submit"][0] < spans["window"][1]]},
        "counters": {"window_start": opened["counters"],
                     "window_end": closed["counters"]},
        "trace": None, "trace_steps": None,
        "serve": {"requests": everyone, "spans": spans, "ended": ended,
                  "setup_phases": phases, "flops": c.flops,
                  "max_slots": workload["server"]["max_slots"],
                  "programs": adapter.program_names(),
                  "traced_span_name": TRACED_SPAN},
    }
    device = harness.device_record(devices, memory_peak)
    breakdown = None
    if args.trace:
        if rehearse:
            say("trace", planes=[f"{d['plane']} / {d['line']}" for d in
                                 trace_reduce.describe(harness.TRACE_DIR,
                                                       top=0)])
        else:
            from chipbench import serve_trace

            # every host event: the serving readers tell the programs
            # apart by the host's dispatches
            ops = trace_reduce.load(harness.TRACE_DIR, host_prefix="")
            ctx["trace"] = ops
            busy = trace_reduce.busy_seconds(ops)
            start, end = trace_reduce.window(ops)
            device["busy_s"] = sum(busy.values()) / len(busy)
            device["window_s"] = end - start
            breakdown = {
                "device_ops": trace_reduce.top_ops(ops),
                "idle_gaps": serve_trace.idle_gaps_by_programs(
                    ops, ctx["serve"]["programs"])}
        if not args.keep_trace:
            shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)

    metrics = {}
    if not rehearse:
        for metric, reader in (per_layer if args.trace else end_to_end):
            value = reader.read(ctx)
            if value is not None:
                metrics[metric["name"]] = {"value": float(value),
                                           "unit": metric["unit"]}
    line = {"correct": bool(compare.ok), "attempted": len(everyone),
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    if rehearse:
        line["rehearsal"] = True
    print("\n".join(compare.lines()), file=sys.stderr)
    line["compared"] = compare.numbers
    return line
