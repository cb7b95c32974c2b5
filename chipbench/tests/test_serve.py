"""The served run on the CPU: the request generator's invariants,
``serve_flops.py`` against hand-worked counts, the two mixes equal but
for their rate, the served window's record through the end-to-end
readers that the training cells use, the reference against the program
(prefill, then decode through the paged cache, mixed slots) in float32,
the fp8 control failing where bfloat16 passes, and runs with the timed
path broken underneath coming out not correct.  (The rehearsal of both
served cells is ``test_run.py``'s, which takes every cell of
BENCHMARK.json.)"""

import collections
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import serve_flops  # noqa: E402
from chipbench.inputs import requests as make_requests  # noqa: E402

CELL = "mistral7b-chat-steady-1chip"


def load(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


STEADY = load("workloads", "chat-steady-1chip.json")
SATURATED = load("workloads", "chat-saturated-1chip.json")
CONFIG = load("configs", "mistral7b-l16-served.json")
PHASES = [("warmup", 5), ("window", 20), ("trace", 6)]


def multiset(requests, phase):
    mine = [r for r in requests if r["phase"] == phase]
    return (collections.Counter(len(r["prompt"]) for r in mine),
            collections.Counter(r["budget"] for r in mine),
            sorted(r["gap"] for r in mine))


def test_every_seed_gets_the_same_multiset_in_another_order():
    a = make_requests.make(7, CONFIG, STEADY["traffic"], PHASES)
    b = make_requests.make(2**31 + 12345, CONFIG, STEADY["traffic"], PHASES)
    for phase, seconds in PHASES:
        assert multiset(a, phase) == multiset(b, phase)
        mine = [r for r in a if r["phase"] == phase]
        assert len(mine) == round(STEADY["traffic"]["rate"] * seconds)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert [r["budget"] for r in a] != [r["budget"] for r in b]
    assert not np.allclose([r["at"] for r in a], [r["at"] for r in b])
    # the same seed gives the same traffic, ids and all
    again = make_requests.make(7, CONFIG, STEADY["traffic"], PHASES)
    assert all(np.array_equal(x["prompt"], y["prompt"]) and x["at"] == y["at"]
               for x, y in zip(a, again))


def test_an_order_seed_replays_one_schedule_with_the_seeds_own_ids():
    traffic = SATURATED["traffic"]
    assert "order_seed" in traffic and "order_seed" not in STEADY["traffic"]
    a = make_requests.make(7, CONFIG, traffic, PHASES)
    b = make_requests.make(2**31 + 12345, CONFIG, traffic, PHASES)
    assert [(r["at"], len(r["prompt"]), r["budget"]) for r in a] \
        == [(r["at"], len(r["prompt"]), r["budget"]) for r in b]
    assert not any(np.array_equal(x["prompt"], y["prompt"])
                   for x, y in zip(a, b))
    # the same multiset as the steady mix's at this rate, and a traced
    # run's further phase leaves the window's schedule as it was
    drawn = make_requests.make(7, CONFIG, dict(STEADY["traffic"],
                                               rate=traffic["rate"]), PHASES)
    assert multiset(a, "window") == multiset(drawn, "window")
    short = make_requests.make(7, CONFIG, traffic, PHASES[:2])
    assert [r["at"] for r in short] == [r["at"] for r in a[:len(short)]]


def test_traffic_keeps_inside_its_phases_and_the_context():
    traffic = STEADY["traffic"]
    requests = make_requests.make(3, CONFIG, traffic, PHASES)
    at = [r["at"] for r in requests]
    assert at == sorted(at) and at[0] > 0 and at[-1] < 31
    start = 0
    for phase, seconds in PHASES:
        mine = [r["at"] for r in requests if r["phase"] == phase]
        assert start <= min(mine) and max(mine) < start + seconds
        start += seconds
    for r in requests:
        assert traffic["prompt"]["min"] <= len(r["prompt"]) \
            <= traffic["prompt"]["max"]
        assert traffic["output"]["min"] <= r["budget"] \
            <= traffic["output"]["max"]
        assert len(r["prompt"]) + r["budget"] <= traffic["max_total"] \
            < STEADY["server"]["max_seq_len"]
        assert 0 <= r["prompt"].min() and r["prompt"].max() \
            < CONFIG["vocab_size"]
    lengths = sorted(len(r["prompt"]) for r in requests
                     if r["phase"] == "window")
    assert 500 < lengths[len(lengths) // 2] < 800       # median 640


def test_gamma_gaps_of_another_shape_sum_to_the_phase():
    gaps = make_requests.gamma_gap_quantiles(50, 0.5, 10.0)
    assert abs(gaps.sum() - 10.0) < 1e-9 and (np.diff(gaps) > 0).all()
    # burstier than Poisson: a wider spread about the same mean
    assert gaps.std() > make_requests.gamma_gap_quantiles(50, 1, 10.0).std()


def test_the_two_mixes_differ_in_rate_order_seed_and_why_alone():
    a, b = json.loads(json.dumps(STEADY)), json.loads(json.dumps(SATURATED))
    assert a.pop("why") != b.pop("why")
    assert a["traffic"].pop("rate") < b["traffic"].pop("rate")
    a.pop("knee", None), b.pop("knee", None)
    # and the saturated mix replays one schedule (requests.py)
    assert b["traffic"].pop("order_seed") \
        == b["rehearsal"]["traffic"].pop("order_seed")
    assert a == b


def test_the_check_covers_every_prompt_bucket_and_two_table_buckets():
    server = STEADY["server"]
    buckets = sorted(server["prompt_buckets"])
    prompts = {next(b for b in buckets if b >= p)
               for p, _ in STEADY["check_requests"]}
    assert prompts == set(buckets)
    tables = set()
    for prompt, budget in STEADY["check_requests"]:
        blocks = -(-(prompt + budget) // server["block_tokens"])
        tables.add(1 << (blocks - 1).bit_length())
    assert len(tables) >= 2
    assert len(STEADY["check_requests"]) <= server["max_slots"]


def test_flops_against_hand_worked_counts():
    config = {"hidden_size": 8, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 2,
              "intermediate_size": 16, "num_hidden_layers": 3,
              "vocab_size": 10, "sliding_window": 4}
    # q 8x8, k and v 8x4 each, o 8x8, three 8x16 matrices
    layer = 64 + 32 + 32 + 64 + 3 * 128
    assert serve_flops.layer_matmul_params(config) == layer == 576
    assert serve_flops.head_params(config) == 80
    # a prompt of 6: positions see 1, 2, 3, 4, 4, 4 keys under window 4
    keys = 1 + 2 + 3 + 4 + 4 + 4
    assert serve_flops.prefill_flops(config, 6) \
        == 3 * (2 * 576 * 6 + 4 * 4 * 2 * keys) + 2 * 80
    # a generated token fed at position 6 sees 4 keys, and is read off
    assert serve_flops.decode_flops(config, 6) \
        == 3 * (2 * 576 + 4 * 4 * 2 * 4) + 2 * 80
    assert serve_flops.decode_flops(config, 1) \
        == 3 * (2 * 576 + 4 * 4 * 2 * 2) + 2 * 80
    # bytes: every matrix once in bf16; a position's keys and values
    assert serve_flops.weight_bytes(config) == 2 * (3 * 576 + 80)
    assert serve_flops.kv_row_bytes(config) == 2 * 2 * 2 * 2 * 3
    assert serve_flops.decode_kv_bytes(config, 1) == 2 * 48
    assert serve_flops.decode_kv_bytes(config, 9) == 4 * 48
    # Mistral-7B's 16 layers: 218.1M a layer, 131.1M in the head
    assert serve_flops.layer_matmul_params(CONFIG) == 218_103_808
    assert serve_flops.head_params(CONFIG) == 131_072_000
    assert serve_flops.kv_row_bytes(CONFIG) == 64 * 1024


def test_the_served_record_feeds_the_training_cells_readers_unedited():
    """``step_ms_p90``, ``mfu_pct`` and the new readers read a served
    window through ``ctx["window"]``: a gap between a stream's tokens is
    a step, the tokens processed are the samples."""
    from chipbench import serve_run
    from chipbench.run import load_reader

    config = dict(CONFIG, num_hidden_layers=1)
    requests = [
        # prefilled before the window, decodes in it
        {"prompt": np.zeros(100, np.int32), "due": -1.0,
         "stamps": [-0.5, 0.1, 0.2, 0.35], "tokens": [1, 2, 3, 4]},
        # arrives and is prefilled in the window, runs past its end
        {"prompt": np.zeros(50, np.int32), "due": 0.2,
         "stamps": [0.5, 0.6, 1.2], "tokens": [5, 6, 7]}]
    record = serve_run.window_record(config, serve_flops, requests,
                                     (0.0, 1.0))
    assert record["emitted"] == 5                   # 3 + 2 stamped inside
    assert record["tokens"] == 50 + 3 + 1           # a prompt, 4 fed back
    assert np.allclose(record["gaps"], [0.6, 0.1, 0.15, 0.1])
    assert record["flops"] == serve_flops.prefill_flops(config, 50) + sum(
        serve_flops.decode_flops(config, p) for p in (100, 101, 102, 50))
    ctx = {"chips": 1, "peaks": {"bf16_flops_per_s": 1e12},
           "flops_per_sample": record["flops"] / record["tokens"],
           "window": {"steps": record["emitted"], "seconds": 1.0,
                      "samples_per_second": record["tokens"] / 1.0,
                      "steps_per_reading": 1,
                      "reading_seconds": record["gaps"]},
           "serve": {"requests": requests, "spans": {"window": (0.0, 1.0)},
                     "ended": 2.0, "setup_phases": {
                         "weights_and_programs_warmup": 5.5, "check": 9.0}}}
    assert load_reader("layer_metrics", "serve_warmup_s").read(ctx) == 5.5
    assert load_reader("layer_metrics", "serve_check_s").read(ctx) == 9.0
    assert load_reader("end_to_end", "mfu_pct").read(ctx) \
        == pytest.approx(100 * record["flops"] / 1e12)
    assert load_reader("end_to_end", "step_ms_p90").read(ctx) \
        == pytest.approx(1e3 * np.quantile(record["gaps"], 0.9))
    assert load_reader("end_to_end", "output_tokens_per_s_per_chip").read(
        ctx) == 5.0
    assert load_reader("layer_metrics", "ttft_ms_p90").read(ctx) \
        == pytest.approx(300.0)                     # one arrival: 0.5 - 0.2
    assert load_reader("end_to_end", "token_gap_ms_p50").read(ctx) \
        == pytest.approx(125.0)


def tool(name, *args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "tools", name),
         *args], cwd=ROOT, env=env, text=True, capture_output=True,
        timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    return [json.loads(line) for line in done.stdout.splitlines()
            if line.startswith("{")]


@pytest.mark.parametrize("fault", ["row_unwritten", "table_shifted",
                                   "window_off_by_one", "neighbour_slot"])
def test_broken_serving_path_is_not_correct(fault):
    """Skips the look for a chip (the rehearsal, in float32, where the
    sound run agrees with the reference to 1e-5: ``test_run.py``) and
    drives the rest of a run with decode broken underneath."""
    line = tool("serve_fault.py", fault, "--workload", CELL, "--seed", "77",
                "--seconds", "1", "--trace", "0", "--rehearse", "1")[-1]
    assert line["correct"] is False and line["failed"] == 0
    over = [name for name, (value, limit) in line["compared"].items()
            if limit is not None and value > limit]
    assert any(name.startswith("check_cache") for name in over)


def test_fp8_control_fails_where_bfloat16_passes():
    """At the rehearsal sizes served in bfloat16, judged by the harness's
    own ``Compare`` under the limits of the cell's chip runs: the sound
    run is correct and the control is not; on every number of the cache
    the control reads three times the sound run or more."""
    points = tool("serve_calibrate.py", "--workload", CELL, "--seeds",
                  "5,6", "--control", "2", "--seconds", "1", "--rehearse",
                  "1", "--dtype", "bfloat16", "--chip-limits", "1")
    assert len(points) == 2
    assert all(p["sound_correct"] is True and p["control_correct"] is False
               for p in points)
    for name in ("check_cache_prefill_rows_worst_layer_gap",
                 "check_cache_decode_rows_worst_layer_gap"):
        sound = max(p["sound"][name] for p in points)
        control = min(p["control"][name] for p in points)
        assert 0 < sound and 3 * sound < control, (name, sound, control)
    assert all(p["failed"] == 0 for p in points)


def test_sweep_reports_attainment_at_each_rate():
    points = tool("sweep_rate.py", "--workload", CELL, "--rates", "5,40",
                  "--seeds", "3", "--seconds", "1", "--rehearse", "1")
    assert [p["rate"] for p in points[:-1]] == [5.0, 40.0]
    assert all(0 <= p["attainment"] <= 1 and p["failed"] == 0
               for p in points[:-1])
    assert points[0]["arrived"] == 5 and points[1]["arrived"] == 40
    assert "knee" in points[-1]


def test_programs_are_told_apart_by_the_hosts_dispatches():
    """Three fingerprints, all ``jit__unknown``: each takes the kind of
    the dispatch most of its runs followed, a run credited to the wrong
    dispatch is outvoted, and the gaps are named by what ran around
    them."""
    from chipbench import serve_trace
    from chipbench.trace_reduce import HOST_LINE, MODULES_LINE, OPS_LINE, Op

    names = {"prefill": "_prefill_fwd", "ingest": "_ingest_fwd",
             "decode": "_decode_fwd"}
    ops, t = [], 0.0
    for tick in range(4):
        # the tick's own small program, which both sides name: the
        # device's clock runs 1.5 ms ahead of the host's
        ops.append(Op(-1, HOST_LINE, "PjitFunction(broadcast_in_dim)",
                      t - .0005, t - .0004))
        ops.append(Op(0, MODULES_LINE, "jit_broadcast_in_dim", t + .001,
                      t + .0011))
        ops.append(Op(-1, HOST_LINE, "PjitFunction(_decode_fwd)", t, t + .001))
        ops.append(Op(0, MODULES_LINE, "jit__unknown(1)", t + .002, t + .102))
        t += 0.11
    ops.append(Op(-1, HOST_LINE, "PjitFunction(_prefill_fwd)", t, t + .001))
    ops.append(Op(0, MODULES_LINE, "jit__unknown(2)", t + .002, t + .052))
    # the ingest's dispatch and the next decode's come before either runs
    ops.append(Op(-1, HOST_LINE, "PjitFunction(_ingest_fwd)", t + .06, t + .061))
    ops.append(Op(-1, HOST_LINE, "PjitFunction(convert_element_type)",
                  t + .0612, t + .0613))
    ops.append(Op(-1, HOST_LINE, "PjitFunction(_decode_fwd)", t + .062, t + .063))
    ops.append(Op(0, MODULES_LINE, "jit__unknown(3)", t + .07, t + .08))
    ops.append(Op(0, MODULES_LINE, "jit__unknown(1)", t + .081, t + .181))
    t += 0.2
    for again in range(2):
        ops.append(Op(-1, HOST_LINE, "PjitFunction(_ingest_fwd)", t, t + .001))
        ops.append(Op(0, MODULES_LINE, "jit__unknown(3)", t + .002, t + .012))
        t += 0.02
    ops.append(Op(0, OPS_LINE, "fusion.1 = fusion f32[1]", 0.002, 0.102))
    kinds = serve_trace.program_kinds(ops, names)
    assert kinds == {"jit__unknown(1)": "decode", "jit__unknown(2)": "prefill",
                     "jit__unknown(3)": "ingest"}
    runs = serve_trace.program_runs(ops, names)
    assert [len(runs[k]) for k in ("prefill", "ingest", "decode")] == [1, 3, 5]
    assert serve_trace.seconds_of(runs["decode"]) == pytest.approx(0.5)
    inside = serve_trace.program_runs(ops, names, (0.0, 0.3))
    assert len(inside["decode"]) == 2 and not inside["prefill"]
    gaps = dict(serve_trace.idle_gaps_by_programs(ops, names))
    assert gaps["host between decode and decode"] == pytest.approx(3 * 0.01)
    assert gaps["host between prefill and ingest"] == pytest.approx(0.018)
    assert "host between decode and prefill" in gaps


def test_a_prefill_runs_request_is_found_past_the_spans_end():
    """Three prefills inside a traced span; the last one's token is
    stamped after the span's end and an earlier request's, prefilled
    before the span, inside it: each run still gets its own request."""
    from chipbench import serve_trace

    offset = 100.0                  # host stamp + offset = trace clock
    requests = [{"stamps": [at - offset], "prompt": [0] * n}
                for at, n in ((9.99, 7), (10.32, 300), (10.9, 40),
                              (15.004, 3000), (15.6, 9))]
    ctx = {"serve": {"requests": requests}}
    runs = {"prefill": [(10.0, 10.31), (10.85, 10.89), (14.7, 14.999)]}
    span = (10.0, 15.0, offset)
    assert [len(r["prompt"]) for r in serve_trace.prefilled(ctx, runs, span)] \
        == [300, 40, 3000]
    # a run whose request left no stamp cannot be told: nothing is read
    assert serve_trace.prefilled(ctx, {"prefill": [(15.7, 15.9)]}, span) \
        is None


def test_program_kinds_on_a_trace_recorded_on_the_chip():
    """``tests/data/serve_events.json``: the modules' runs and the
    host's dispatches of a 5 s traced span of the steady cell (PR 42,
    ``--keep-trace 1``).  The device's clock leads the host's by 1.2 ms
    there, more than lies between two of a tick's dispatches."""
    from chipbench import serve_trace
    from chipbench.trace_reduce import Op

    with open(os.path.join(ROOT, "chipbench", "tests", "data",
                           "serve_events.json")) as f:
        ops = [Op(*row) for row in json.load(f)]
    names = {"prefill": "_prefill_fwd", "ingest": "_ingest_fwd",
             "decode": "_decode_fwd"}
    runs = serve_trace.program_runs(ops, names)
    # five prefill programs (13, 23, 52, 118 and 314 ms: the five prompt
    # buckets), the first run cut by the trace's start
    assert [len(runs[k]) for k in ("prefill", "ingest", "decode")] \
        == [12, 12, 47]
    assert serve_trace.seconds_of(runs["decode"]) / 47 \
        == pytest.approx(0.08123, rel=1e-3)
    assert serve_trace.seconds_of(runs["prefill"]) \
        == pytest.approx(0.9175, rel=1e-3)
    # without the programs both sides name there is no offset: nothing
    # is told apart, and the readers return nothing
    bare = [op for op in ops if "broadcast_in_dim" not in op.name
            and "convert_element_type" not in op.name]
    assert serve_trace.program_kinds(bare, names) == {}
