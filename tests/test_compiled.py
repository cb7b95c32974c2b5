"""Compiled-step (in-program) collective tests — the analogue of the
reference's XLA-ops tests (``test/parallel/test_tensorflow.py``
HorovodAllreduce-under-jit cases, ``xla_mpi_ops.cc:185-307`` path):
grouped allreduce as one XLA program, and the fully-compiled train
step."""

import numpy as np
import optax
import pytest

import horovod_tpu as hvd


NP = 4


def run_ranks(fn, np_ranks=NP):
    return hvd.run(fn, np=np_ranks)


def test_compiled_allreduce_average(hvd_shutdown):
    def fn():
        r = hvd.rank()
        x = np.arange(8, dtype=np.float32) * (r + 1)
        out = hvd.compiled_allreduce(x)
        expected = np.arange(8, dtype=np.float32) * \
            np.mean([i + 1 for i in range(NP)])
        assert np.allclose(out, expected)
        out -= 1.0          # results must be writable
        return True

    assert all(run_ranks(fn))


def test_compiled_allreduce_sum_matches_engine(hvd_shutdown):
    def fn():
        r = hvd.rank()
        x = np.arange(16, dtype=np.float64) + r
        fast = hvd.compiled_allreduce(x, op=hvd.Sum)
        slow = hvd.allreduce(x, op=hvd.Sum)
        assert np.allclose(fast, slow)
        return True

    assert all(run_ranks(fn))


def test_compiled_grouped_mixed_dtypes(hvd_shutdown):
    """One program reduces a mixed f32/f64/int32 group (per-dtype
    fusion packing, reference fusion-buffer role)."""
    def fn():
        r = hvd.rank()
        arrs = [np.ones((3, 4), np.float32) * (r + 1),
                np.full((5,), float(r), np.float64),
                np.arange(6, dtype=np.int32) * (r + 1),
                np.ones((2, 2), np.float32) * r]
        outs = hvd.compiled_grouped_allreduce(arrs, op=hvd.Sum)
        s = NP
        tri = sum(range(1, NP + 1))
        assert np.allclose(outs[0], np.ones((3, 4)) * tri)
        assert np.allclose(outs[1], np.full((5,), sum(range(NP))))
        assert np.array_equal(outs[2], np.arange(6) * tri)
        assert np.allclose(outs[3], np.ones((2, 2)) * sum(range(NP)))
        assert outs[0].dtype == np.float32 and outs[1].dtype == np.float64
        assert outs[2].dtype == np.int32
        return True

    assert all(run_ranks(fn))


def test_compiled_allreduce_prescale_postscale(hvd_shutdown):
    """The gpf split (pre=1/f, post=f) must cancel for Average."""
    def fn():
        r = hvd.rank()
        x = np.ones(4, np.float32) * (r + 1)
        out = hvd.compiled_allreduce(x, prescale_factor=0.5,
                                     postscale_factor=2.0)
        assert np.allclose(out, np.mean([i + 1 for i in range(NP)]))
        return True

    assert all(run_ranks(fn))


def test_compiled_allreduce_int_average_rejected(hvd_shutdown):
    def fn():
        with pytest.raises(ValueError):
            hvd.compiled_allreduce(np.arange(4, dtype=np.int32),
                                   op=hvd.Average)
        return True

    assert all(run_ranks(fn))


def test_compiled_allreduce_unsupported_op(hvd_shutdown):
    def fn():
        with pytest.raises(ValueError):
            hvd.compiled_allreduce(np.ones(4, np.float32), op=hvd.Min)
        return True

    assert all(run_ranks(fn))


def test_compiled_allreduce_process_set(hvd_shutdown):
    """Compiled collectives scope to a process set's sub-mesh."""
    def fn():
        ps = hvd.add_process_set([0, 1])
        r = hvd.rank()
        if r in (0, 1):
            out = hvd.compiled_allreduce(
                np.ones(4, np.float32) * (r + 1), process_set=ps)
            assert np.allclose(out, 1.5)
        hvd.barrier()
        return True

    assert all(run_ranks(fn))


def test_compiled_train_step_matches_single_rank(hvd_shutdown):
    """The one-program train step must equal serial SGD on the
    concatenated global batch (Average semantics)."""
    W0 = np.ones((3, 1), np.float32)

    def loss_fn(params, batch):
        import jax.numpy as jnp
        x, y = batch
        pred = x @ params["w"]
        return jnp.mean((pred - y) ** 2)

    def make_data(r):
        rng = np.random.RandomState(r)
        x = rng.rand(8, 3).astype(np.float32)
        y = (x.sum(axis=1, keepdims=True)).astype(np.float32)
        return x, y

    def fn():
        step = hvd.make_compiled_train_step(loss_fn, optax.sgd(0.1))
        state = step.init_state({"w": W0.copy()})
        x, y = make_data(hvd.rank())
        for _ in range(5):
            state, loss = step(state, (x, y))
        return np.asarray(state["params"]["w"]), float(loss)

    results = run_ranks(fn)
    ws = [w for w, _ in results]
    # every rank holds identical (replicated) params
    for w in ws[1:]:
        assert np.allclose(w, ws[0], atol=1e-6)

    # serial reference: average of per-rank grads == grad of mean loss
    import jax
    import jax.numpy as jnp

    def serial_loss(w, batches):
        losses = [jnp.mean((x @ w - y) ** 2) for x, y in batches]
        return jnp.mean(jnp.stack(losses))

    batches = [make_data(r) for r in range(NP)]
    w = jnp.asarray(W0)
    for _ in range(5):
        g = jax.grad(serial_loss)(w, batches)
        w = w - 0.1 * g
    assert np.allclose(ws[0], np.asarray(w), atol=1e-5), \
        (ws[0].ravel(), np.asarray(w).ravel())


def test_compiled_train_step_sum_op(hvd_shutdown):
    def loss_fn(params, batch):
        import jax.numpy as jnp
        return jnp.sum(params["w"] * batch)

    def fn():
        step = hvd.make_compiled_train_step(
            loss_fn, optax.sgd(1.0), op=hvd.Sum)
        state = step.init_state({"w": np.zeros(3, np.float32)})
        batch = np.ones(3, np.float32) * (hvd.rank() + 1)
        state, _ = step(state, batch)
        # grad per rank = batch; summed = sum(r+1); w = -sum
        expected = -np.ones(3) * sum(range(1, NP + 1))
        assert np.allclose(np.asarray(state["params"]["w"]), expected)
        return True

    assert all(run_ranks(fn))


def test_compiled_reducer_reuses_programs(hvd_shutdown):
    """Steady state hits the program cache (response-cache role)."""
    def fn():
        red = hvd.CompiledGroupedAllreduce(op=hvd.Sum)
        x = [np.ones(4, np.float32) * hvd.rank()]
        red(x)
        n1 = len(red._programs)
        red(x)
        red([np.ones(4, np.float32)])      # same signature
        assert len(red._programs) == n1 == 1
        red([np.ones(5, np.float32)])      # new signature -> new program
        assert len(red._programs) == 2
        return True

    assert all(run_ranks(fn))


def test_compiled_reducer_survives_reinit(hvd_shutdown):
    """A long-lived reducer must not serve programs compiled for a
    previous engine's world size after shutdown + re-init."""
    red = hvd.CompiledGroupedAllreduce(op=hvd.Average)

    def fn4():
        return red([np.ones(4, np.float32) * (hvd.rank() + 1)])[0]

    outs = hvd.run(fn4, np=4)
    assert all(np.allclose(o, 2.5) for o in outs)
    hvd.shutdown()

    def fn2():
        return red([np.ones(4, np.float32) * (hvd.rank() + 1)])[0]

    outs = hvd.run(fn2, np=2)
    # average over the NEW world of 2, not the stale 4
    assert all(np.allclose(o, 1.5) for o in outs), outs


def test_compiled_train_step_has_aux(hvd_shutdown):
    """aux (mutable model state, e.g. BN stats) threads through the
    step and float leaves are cross-replica averaged."""
    import jax.numpy as jnp

    def loss_fn(params, aux, batch):
        loss = jnp.mean((batch @ params["w"]) ** 2)
        new_aux = {"running": aux["running"] * 0.9
                   + 0.1 * jnp.mean(batch),
                   "count": aux["count"] + 1}
        return loss, new_aux

    def fn():
        step = hvd.make_compiled_train_step(
            loss_fn, optax.sgd(0.01), has_aux=True)
        state = step.init_state(
            {"w": np.ones((3, 1), np.float32)},
            aux={"running": np.zeros((), np.float32),
                 "count": np.zeros((), np.int32)})
        batch = np.full((2, 3), float(hvd.rank()), np.float32)
        state, loss = step(state, batch)
        return (float(state["aux"]["running"]),
                int(state["aux"]["count"]), float(loss))

    results = run_ranks(fn)
    runnings = [r[0] for r in results]
    # pmean of 0.1*mean(batch)=0.1*r over ranks = 0.1*mean(r)
    expected = 0.1 * np.mean(range(NP))
    assert all(np.isclose(v, expected) for v in runnings), runnings
    assert all(r[1] == 1 for r in results)


def test_compiled_allreduce_signature_mismatch_raises(hvd_shutdown):
    """Mismatched shapes across rank threads fail loudly on every rank
    (the engine path negotiates this; the compiled path checks at the
    rendezvous) instead of hanging or silently mis-reducing."""
    def fn():
        n = 4 if hvd.rank() == 0 else 5
        with pytest.raises((ValueError, RuntimeError)) as ei:
            hvd.compiled_allreduce(np.ones(n, np.float32))
        assert "signature mismatch" in str(ei.value)
        return True

    assert all(run_ranks(fn))


def test_device_feeder_pipeline(hvd_shutdown):
    """DeviceFeeder stages batches ahead of the step (single-rank
    process shape: place_batch is per-process)."""
    import jax.numpy as jnp
    from horovod_tpu.data import DeviceFeeder

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    hvd.init(num_ranks=1)
    step = hvd.make_compiled_train_step(loss_fn, optax.sgd(0.1))
    state = step.init_state({"w": np.zeros((3, 1), np.float32)})

    rng = np.random.RandomState(0)

    def batches():
        for _ in range(6):
            x = rng.rand(8, 3).astype(np.float32)
            yield x, x.sum(axis=1, keepdims=True).astype(np.float32)

    losses = []
    with DeviceFeeder(step, batches(), prefetch=2) as feeder:
        for staged in feeder:
            state, loss = step(state, staged)
            losses.append(float(loss))
    assert len(losses) == 6
    assert losses[-1] < losses[0]


def test_device_feeder_surfaces_source_errors(hvd_shutdown):
    from horovod_tpu.data import DeviceFeeder

    def loss_fn(params, batch):
        import jax.numpy as jnp
        return jnp.mean(batch * params["w"])

    hvd.init(num_ranks=1)
    step = hvd.make_compiled_train_step(loss_fn, optax.sgd(0.1))

    def bad_batches():
        yield np.ones(3, np.float32)
        raise RuntimeError("source broke")

    got = []
    with pytest.raises(RuntimeError, match="source broke"):
        for staged in DeviceFeeder(step, bad_batches()):
            got.append(staged)
    assert len(got) == 1


def test_device_feeder_close_joins_thread(hvd_shutdown):
    """close() must not deadlock the staging thread: with prefetch=1
    and an unconsumed queue, the blocked put used to refill the slot
    close() had just drained and then hang on the sentinel put forever
    (round-3 advisor finding)."""

    from horovod_tpu.data import DeviceFeeder

    class FakeStep:
        def place_batch(self, batch):
            return batch

    def endless():
        i = 0
        while True:
            yield i
            i += 1

    feeder = DeviceFeeder(FakeStep(), endless(), prefetch=1)
    it = iter(feeder)
    next(it)                      # thread is now blocked on a full queue
    feeder.close()
    assert not feeder._thread.is_alive()
    # a consumer resuming after close() sees clean exhaustion, not a
    # permanently-blocked get()
    with pytest.raises(StopIteration):
        next(it)
    # idempotent: a second close is harmless
    feeder.close()


def test_compiled_step_state_checkpoints(hvd_shutdown, tmp_path):
    """Compiled-step train state round-trips through the sharded
    CheckpointManager: save mid-training, restore, resume — resumed
    replicas match an uninterrupted run."""
    import jax.numpy as jnp
    from horovod_tpu.utils import CheckpointManager

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    hvd.init(num_ranks=1)
    rng = np.random.RandomState(3)
    data = [(rng.rand(8, 3).astype(np.float32),) * 1 for _ in range(6)]
    batches = [(x[0], x[0].sum(axis=1, keepdims=True)) for x in data]

    step = hvd.make_compiled_train_step(loss_fn, optax.adam(0.05),
                                        donate=False)
    state = step.init_state({"w": np.zeros((3, 1), np.float32)})
    for b in batches[:3]:
        state, _ = step(state, b)
    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr.save(3, state)

    # uninterrupted continuation
    ref = state
    for b in batches[3:]:
        ref, _ = step(ref, b)

    # restore + resume
    import jax
    restored = mgr.restore(3, target=jax.tree.map(np.asarray, state))
    for b in batches[3:]:
        restored, _ = step(restored, b)
    for a, c in zip(jax.tree.leaves(ref), jax.tree.leaves(restored)):
        assert np.allclose(np.asarray(a), np.asarray(c), atol=1e-6)


def test_compiled_train_step_adasum(hvd_shutdown):
    """op=Adasum inside the one-program step matches the engine's
    Adasum allreduce of the same per-rank gradients."""
    import jax
    import jax.numpy as jnp

    def loss_fn(params, batch):
        return jnp.sum(params["w"] * batch)

    def fn():
        r = hvd.rank()
        batch = (np.arange(1, 5, dtype=np.float32)) * (r + 1)
        # engine reference: adasum-allreduce the analytic grad (=batch)
        ref = hvd.allreduce(batch.copy(), op=hvd.Adasum,
                            name="ada_ref")
        step = hvd.make_compiled_train_step(
            loss_fn, optax.sgd(1.0), op=hvd.Adasum)
        state = step.init_state({"w": np.zeros(4, np.float32)})
        state, _ = step(state, batch)
        # w = -combined_grad with lr 1.0
        got = -np.asarray(state["params"]["w"])
        assert np.allclose(got, np.asarray(ref), atol=1e-5), \
            (got, np.asarray(ref))
        return True

    assert all(run_ranks(fn))


# ---------------------------------------------------------------------------
# the compiled step accounts for itself: scopes, report, phases, stages

STEP_SCOPES = ("hvd_step/loss_and_grad", "hvd_step/grad_reduce",
               "hvd_step/optimizer")


def _tiny_lm():
    """(loss_fn, params, tokens) of a two-layer LM with the flash
    kernel (interpreted) and the fused chunked cross-entropy."""
    import functools

    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import (TransformerConfig, TransformerLM,
                                    make_fused_lm_loss)
    from horovod_tpu.ops.pallas_kernels import flash_attention

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=64, dtype=jnp.float32, remat=True,
        remat_policy="dots_flash")
    model = TransformerLM(cfg, attention_fn=functools.partial(
        flash_attention, interpret=True, block_q=32, block_k=32))
    tokens = np.arange(2 * 64, dtype=np.int32).reshape(2, 64) % 64
    params = jax.device_get(model.init(
        jax.random.PRNGKey(0), jnp.asarray(tokens))["params"])
    return make_fused_lm_loss(model, n_chunks=2), params, tokens


def _aux_problem():
    import jax.numpy as jnp

    def loss_fn(params, aux, batch):
        loss = jnp.mean((batch @ params["w"]) ** 2)
        return loss, {"running": aux["running"] * 0.9
                      + 0.1 * jnp.mean(batch)}

    return (loss_fn, {"w": np.ones((3, 1), np.float32)},
            {"running": np.zeros((), np.float32)},
            np.ones((2, 3), np.float32))


def _make(model, **kw):
    """(step, state, batch) of one of the two small problems."""
    if model == "lm":
        loss_fn, params, batch = _tiny_lm()
        step = hvd.make_compiled_train_step(
            loss_fn, optax.adamw(1e-3), **kw)
        return step, step.init_state(params), batch
    loss_fn, params, aux, batch = _aux_problem()
    step = hvd.make_compiled_train_step(
        loss_fn, optax.sgd(0.01), has_aux=True, **kw)
    return step, step.init_state(params, aux=aux), batch


@pytest.mark.parametrize("model", ["lm", "has_aux"])
def test_step_scopes_in_lowered_text(hvd_shutdown, model):
    """The parts of the step body are named in what the compiler is
    handed (one rank: the stacked program)."""
    hvd.init(num_ranks=1)
    step, state, batch = _make(model)
    text = step.lower(state, step.place_batch(batch)).as_text(
        debug_info=True)
    wanted = STEP_SCOPES + (("hvd_step/aux_reduce",)
                            if model == "has_aux" else
                            ("lm_head_ce", "embed", "flash_fwd",
                             "flash_dkv"))
    for scope in wanted:
        assert scope in text, scope
    # the flash backward is ONE kernel, under the old dkv scope
    assert "flash_dq" not in text
    if model == "lm":
        # the head forms its gradient where it forms its logits: its
        # projection (the one einsum of that spelling, which the
        # lowered text names relative to the function that holds it)
        # is not computed a second time in the backward
        assert "bcm,vm->bcv/dot_general" in text
        assert "rematted_computation/bcm,vm->bcv" not in text


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["plain", "sharded"])
@pytest.mark.parametrize("model", ["lm", "has_aux"])
def test_step_scopes_in_program_table(hvd_shutdown, model, sharded):
    """...and in the optimized program's own table, under rank
    threads (the shard_map program), plain and weight-update
    sharded; forward, backward and recomputation are told apart by
    jax's own names inside ``loss_and_grad``."""
    def fn():
        step, state, batch = _make(model, sharded=sharded)
        assert step.report() is None
        state, _ = step(state, batch)
        paths = set(step.report()["scopes"].values())
        wanted = STEP_SCOPES + (("hvd_step/aux_reduce",)
                                if model == "has_aux" else ())
        missing = [s for s in wanted
                   if not any(s in p for p in paths)]
        in_grad = [p for p in paths if "hvd_step/loss_and_grad" in p]
        kinds = (any("transpose(" in p for p in in_grad),
                 any("jvp(" in p and "transpose(" not in p
                     for p in in_grad),
                 any("rematted_computation" in p for p in in_grad))
        head_remat = [p for p in paths if "lm_head_ce" in p
                      and "rematted_computation" in p]
        return missing, kinds, head_remat

    for missing, (backward, forward, remat), head_remat in run_ranks(
            fn, 2):
        assert not missing, missing
        assert backward and forward
        # the layers' recomputation; none of it is the loss head's
        assert remat == (model == "lm")
        assert not head_remat, head_remat


def test_step_report_table_memory_and_laziness(hvd_shutdown):
    """``report()``: nothing before the first call, nothing computed
    until asked, a table that covers every instruction of the
    optimized module, a memory account whose parts are non-negative
    and whose arguments are the state and the batch."""
    import re

    import jax

    hvd.init(num_ranks=1)
    step, state, batch = _make("lm")
    assert step.report() is None
    staged = step.place_batch(batch)
    for _ in range(2):
        state, _ = step(state, staged)
    assert step._prog._report is None       # nobody asked yet
    report = step.report()
    assert step.report() is report          # computed once, then kept
    assert report["module"] == "jit_prog"

    text = step.lower(state, staged).compile().as_text()
    computations = set(re.findall(
        r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$", text, re.M))
    instructions = set(re.findall(r"%([\w.\-]+)", text)) - computations
    assert instructions and instructions <= set(report["scopes"])
    # a fusion the compiler made is booked to an op of the program
    fusions = [n for n in report["scopes"] if "fusion" in n]
    assert fusions and all(report["scopes"][n] for n in fusions)
    # one rank on the CPU: no compiler's kernel, no collective
    assert report["renamed"] == {} and report["collectives"] == []

    memory = report["memory"]
    assert all(v >= 0 for v in memory.values())
    held = sum(leaf.nbytes for leaf in jax.tree.leaves(
        (state, staged.tree)))
    assert memory["argument"] == held
    assert 0 < memory["alias"] <= memory["output"]
    assert report["cost"]["flops"] > 0


def test_instruction_scopes_books_a_fusion_to_its_root():
    from horovod_tpu.telemetry.programs import instruction_scopes

    text = """HloModule jit_prog, is_scheduled=true

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %sin.0 = f32[8]{0} sine(%p), metadata={op_name="jit(prog)/a/sin"}
  ROOT %mul.0 = f32[8]{0} multiply(%sin.0, %sin.0), metadata={op_name="jit(prog)/b/mul" stack_frame_id=2}
}

%fused_computation.1 (q: f32[8]) -> (f32[8], f32[8]) {
  %q = f32[8]{0} parameter(0)
  %neg.0 = f32[8]{0} negate(%q), metadata={op_name="jit(prog)/c/neg"}
  ROOT %tuple.0 = (f32[8]{0}, f32[8]{0}) tuple(%neg.0, %q)
}

ENTRY %main.3 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %fusion.2 = (f32[8]{0}, f32[8]{0}) fusion(%x), kind=kLoop, calls=%fused_computation.1
  %own.3 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(prog)/d/own"}
  %copy-start.5 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(f32[8]{0:T(8)(2,1)} %own.3), cross_program_prefetch_index=0
  %copy-done.5 = f32[8]{0:S(1)} copy-done(%copy-start.5)
  %gte.6 = f32[8]{0} get-tuple-element(%fusion.2), index=0
  %convert.7 = bf16[8]{0} convert(%x)
  %constant.8 = f32[] constant(0)
  %broadcast.9 = f32[8]{0} broadcast(%constant.8), dimensions={}
  ROOT %copy.4 = f32[8]{0} copy(%fusion.1)
}
"""
    scopes = instruction_scopes(text)
    assert scopes["fusion.1"] == "jit(prog)/b/mul"      # the root's
    assert scopes["fusion.2"] == "jit(prog)/c/neg"      # root has none
    assert scopes["own.3"] == "jit(prog)/d/own"         # its own first
    assert scopes["sin.0"] == "jit(prog)/a/sin"
    # what only moves a result belongs to who made it, hop by hop
    assert scopes["copy.4"] == "jit(prog)/b/mul"
    assert scopes["copy-start.5"] == scopes["copy-done.5"] \
        == "jit(prog)/d/own"
    assert scopes["gte.6"] == "jit(prog)/c/neg"
    # what computes something new without a name stays unnamed
    assert scopes["convert.7"] == scopes["broadcast.9"] == ""
    assert set(scopes) == {"p", "sin.0", "mul.0", "q", "neg.0",
                           "tuple.0", "x", "fusion.1", "fusion.2",
                           "own.3", "copy.4", "copy-start.5",
                           "copy-done.5", "gte.6", "convert.7",
                           "constant.8", "broadcast.9"}


def _step_counters():
    from horovod_tpu import telemetry

    return {name.split("horovod_step_")[1]: telemetry.counter_total(name)
            for name in (telemetry.STEP_CALLS_FAMILY,
                         telemetry.STEP_RENDEZVOUS_WAIT_FAMILY,
                         telemetry.STEP_STAGE_BATCH_FAMILY,
                         telemetry.STEP_STAGED_BYTES_FAMILY,
                         telemetry.STEP_PROGRAM_CALL_FAMILY)}


def test_step_call_counters_one_rank(hvd_shutdown):
    hvd.init(num_ranks=1)
    step, state, batch = _make("has_aux")
    before = _step_counters()
    for _ in range(3):
        state, _ = step(state, batch)           # a host batch: staged
    mid = _step_counters()
    staged = step.place_batch(batch)
    for _ in range(2):
        state, _ = step(state, staged)          # placed once: not staged
    after = _step_counters()
    assert mid["calls_total"] - before["calls_total"] == 3
    assert after["calls_total"] - mid["calls_total"] == 2
    assert mid["stage_batch_seconds_total"] \
        > before["stage_batch_seconds_total"]
    assert mid["staged_bytes_total"] - before["staged_bytes_total"] \
        == 3 * batch.nbytes
    # place_batch stages once more; the steps after it stage nothing
    assert after["staged_bytes_total"] - mid["staged_bytes_total"] \
        == batch.nbytes
    assert after["program_call_seconds_total"] \
        > mid["program_call_seconds_total"] \
        > before["program_call_seconds_total"]
    assert after["rendezvous_wait_seconds_total"] == 0


def test_step_call_counters_rank_threads(hvd_shutdown):
    """Under rank threads every rank's call counts, the launching rank
    stages and calls once a step for all, and the rendezvous books the
    skew between the threads' arrivals."""
    import time

    def fn():
        step, state, batch = _make("has_aux")
        state, _ = step(state, batch)       # the compile, out of the way
        before = _step_counters()
        for _ in range(3):
            if hvd.rank() == 1:
                time.sleep(0.05)            # rank 0 waits for rank 1
            state, _ = step(state, batch)
        return before, _step_counters(), batch.nbytes

    for before, after, nbytes in run_ranks(fn, 2):
        # the other rank's reads fall between the two, so bound them
        assert 5 <= after["calls_total"] - before["calls_total"] <= 7
        assert 0.05 * 2 < after["rendezvous_wait_seconds_total"] \
            - before["rendezvous_wait_seconds_total"] < 0.05 * 3 + 1.0
        assert after["staged_bytes_total"] - before["staged_bytes_total"] \
            in (2 * 2 * nbytes, 3 * 2 * nbytes, 4 * 2 * nbytes)
        assert after["stage_batch_seconds_total"] \
            > before["stage_batch_seconds_total"]
        assert after["program_call_seconds_total"] \
            > before["program_call_seconds_total"]


def test_compile_stage_counters_first_call_only(hvd_shutdown):
    from horovod_tpu import telemetry

    stages = (telemetry.COMPILE_TRACE_SECONDS_FAMILY,
              telemetry.COMPILE_LOWER_SECONDS_FAMILY,
              telemetry.COMPILE_BACKEND_SECONDS_FAMILY,
              telemetry.COMPILE_CACHE_READ_SECONDS_FAMILY)

    def read():
        return [telemetry.counter_total(n) for n in stages
                + (telemetry.COMPILE_SECONDS_FAMILY,)]

    hvd.init(num_ranks=1)
    step, state, batch = _make("lm")
    assert read()[:3] == [0.0, 0.0, 0.0]
    state, _ = step(state, batch)
    first = read()
    assert all(v > 0 for v in first[:3]), first
    # each instant is booked to one stage: they fit in the first call
    assert sum(first[:4]) <= first[4]
    state, _ = step(state, batch)
    assert read() == first


def test_compile_stage_listener_registered_once(hvd_shutdown):
    from jax._src import monitoring

    from horovod_tpu.telemetry import programs

    for _ in range(2):
        hvd.init(num_ranks=1)
        step, state, batch = _make("has_aux")
        step(state, batch)
        hvd.shutdown()
    assert monitoring.get_event_duration_listeners().count(
        programs._on_duration) == 1
    assert monitoring.get_event_listeners().count(
        programs._on_event) == 1


# ---------------------------------------------------------------------------
# gradients reduced where the backward pass completes them (ops/grad_hook.py)

def _scanned_lm(policy, n_layers=3):
    """(model, loss_fn, params) of a small scanned LM in float32."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import (TransformerConfig, TransformerLM,
                                    make_fused_lm_loss)

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2,
        n_layers=n_layers, d_ff=64, max_seq_len=16, dtype=jnp.float32,
        remat=True, remat_policy=policy)
    model = TransformerLM(cfg)
    params = jax.device_get(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))["params"])
    return model, make_fused_lm_loss(model, n_chunks=2), params


def _rank_tokens(rank):
    return (np.arange(2 * 16, dtype=np.int32).reshape(2, 16) * (rank + 3)
            + rank) % 64


def _without_hooks(monkeypatch):
    """The parent's order of operations: the step opens no context, so
    every gradient is reduced after the backward pass."""
    import contextlib

    from horovod_tpu.ops import grad_hook

    monkeypatch.setattr(grad_hook, "reducing_in_backward",
                        lambda *a: contextlib.nullcontext())


def _tree_bytes(tree):
    import jax

    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(tree))


def _reduce_counters():
    from horovod_tpu import telemetry

    return (telemetry.counter_total(
                telemetry.STEP_GRAD_REDUCE_BYTES_FAMILY),
            telemetry.counter_total(
                telemetry.STEP_GRAD_REDUCE_IN_BACKWARD_BYTES_FAMILY))


@pytest.mark.parametrize("policy", ["full", "dots_flash"])
@pytest.mark.parametrize("op", [hvd.Average, hvd.Sum],
                         ids=["average", "sum"])
def test_grad_reduced_in_backward_matches_after(hvd_shutdown, monkeypatch,
                                                op, policy):
    """Four ranks, a scanned LM through the fused loss: the loss, the
    first gradient (AdamW's first moment) and the state after two
    steps are the parent's to float32 rounding, with ``op=Sum`` the sum
    and not four times it; the counters hold the exact bytes."""
    import jax

    _, loss_fn, params = _scanned_lm(policy)

    def fn():
        step = hvd.make_compiled_train_step(
            loss_fn, optax.adamw(1e-2), op=op)
        state = step.init_state(params)
        batch = _rank_tokens(hvd.rank())
        before = _reduce_counters()
        state, loss1 = step(state, batch)
        first_moment = jax.device_get(state["opt_state"][0].mu)
        state, loss2 = step(state, batch)
        return (float(loss1), float(loss2), first_moment,
                jax.device_get(state["params"]), before,
                _reduce_counters())

    hooked = run_ranks(fn)
    _without_hooks(monkeypatch)
    plain = run_ranks(fn)

    def close(a, b):
        jax.tree.map(lambda x, y: np.testing.assert_allclose(
            x, y, rtol=2e-5, atol=1e-7), a, b)

    for mine, theirs in zip(hooked, plain):
        close(mine[:4], theirs[:4])
    # the exact bytes, twice (two program calls), on every rank's read
    everything, layers = _tree_bytes(params), _tree_bytes(params["layers"])
    assert 0.5 < layers / everything < 1
    for (*_, before, after), (*_, p_before, p_after) in zip(hooked, plain):
        assert (after[0] - before[0], after[1] - before[1]) \
            == (2 * everything, 2 * layers)
        assert (p_after[0] - p_before[0], p_after[1] - p_before[1]) \
            == (2 * everything, 0)


def test_grad_reduced_in_backward_sum_is_four_averages(hvd_shutdown):
    """No leaf is reduced twice: the summed gradient is four times the
    averaged one, leaf for leaf (a second ``psum`` would make the
    layers' sixteen times it)."""
    import jax

    _, loss_fn, params = _scanned_lm("full")

    def fn(op):
        step = hvd.make_compiled_train_step(
            loss_fn, optax.sgd(1.0), op=op, donate=False)
        state = step.init_state(params)
        new, _ = step(state, _rank_tokens(hvd.rank()))
        return jax.device_get(jax.tree.map(
            lambda a, b: a - b, state["params"], new["params"]))

    summed = run_ranks(lambda: fn(hvd.Sum))[0]
    averaged = run_ranks(lambda: fn(hvd.Average))[0]
    jax.tree.map(lambda s, a: np.testing.assert_allclose(
        s, 4 * a, rtol=1e-4, atol=1e-6), summed, averaged)


def _all_reduces(text):
    """[(inside a loop body, result type)] of the all-reduces of an
    optimized module's text."""
    import re

    found = []
    for line in text.splitlines():
        hit = re.search(r" = (.*?) all-reduce(?:-start)?\(", line)
        if hit:
            found.append(("while/body" in line, hit.group(1)))
    return found


@pytest.mark.parametrize("policy", ["full", "dots_flash"])
def test_grad_reduce_sits_in_the_backward_loop(hvd_shutdown, policy):
    """The optimized four-rank program reduces the per-layer shapes
    inside the backward loop's body and no stacked ``layers/`` shape
    after it; what is left after the loop is the embedding and the
    final norm."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.ops.xla_ops import MeshExecutor

    _, loss_fn, params = _scanned_lm(policy)
    opt = optax.adamw(1e-2)
    ex = MeshExecutor(jax.devices()[:4], 4)
    assert ex.shard_mode
    step = hvd.make_compiled_train_step(loss_fn, opt)
    state = jax.device_put({"params": params, "opt_state": opt.init(params)},
                           NamedSharding(ex.mesh, P()))
    batch = jax.device_put(np.stack([_rank_tokens(r) for r in range(4)]),
                           NamedSharding(ex.mesh, P("hvd")))
    text = step._build(ex).lower(state, batch).compile().as_text()

    def dims(shape):
        return "[" + ",".join(map(str, shape)) + "]"

    stacked = {dims(leaf.shape)
               for leaf in jax.tree.leaves(params["layers"])}
    per_layer = {dims(leaf.shape[1:])
                 for leaf in jax.tree.leaves(params["layers"])}
    in_loop = " ".join(t for inside, t in _all_reduces(text) if inside)
    after = " ".join(t for inside, t in _all_reduces(text) if not inside)
    assert all(shape in in_loop for shape in per_layer), in_loop
    assert not any(shape in after for shape in stacked), after
    assert dims(params["embed"].shape) in after


def _hook_traces(report):
    """Paths of a program's table that the hook would have written: a
    gradient reduction inside the loss-and-gradient scope."""
    return [p for p in report["scopes"].values()
            if "hvd_step/loss_and_grad" in p and "hvd_step/grad_reduce" in p]


@pytest.mark.parametrize("case", ["one_rank", "sharded", "adasum"])
def test_no_hook_where_no_step_reduces_alone(hvd_shutdown, monkeypatch,
                                             case):
    """With one rank, with ``sharded=True`` and with ``op=Adasum`` the
    hook returns its argument itself: it is never entered, the
    program's table holds no reduction inside the backward, the
    counters stay where they were, and (one rank) the lowered text is
    the parent's, character for character."""
    from horovod_tpu.ops import grad_hook

    entered = []
    real = grad_hook._reduce_cotangent
    monkeypatch.setattr(
        grad_hook, "_reduce_cotangent",
        lambda *a: entered.append(a) or real(*a))
    _, loss_fn, params = _scanned_lm("full")
    kw = {"sharded": {"sharded": True}, "adasum": {"op": hvd.Adasum},
          "one_rank": {}}[case]

    def fn():
        step = hvd.make_compiled_train_step(loss_fn, optax.adamw(1e-2), **kw)
        state = step.init_state(params)
        before = _reduce_counters()
        state, _ = step(state, _rank_tokens(hvd.rank()))
        return _hook_traces(step.report()), before, _reduce_counters()

    if case == "one_rank":
        hvd.init(num_ranks=1)
        results = [fn()]

        def lowered():
            step = hvd.make_compiled_train_step(loss_fn, optax.adamw(1e-2))
            return step.lower(
                step.init_state(params),
                step.place_batch(_rank_tokens(0))).as_text()

        mine = lowered()
        _without_hooks(monkeypatch)
        assert mine == lowered()
    else:
        results = run_ranks(fn, 2)
    assert not entered
    for traces, before, after in results:
        assert not traces
        # Adasum across devices reduces every byte, none in the backward
        assert after[1] == before[1]
        assert (after[0] > before[0]) == (case == "adasum")


def test_hooked_path_the_params_lack_is_refused(hvd_shutdown):
    """A hook that covers a subtree the step's parameters do not hold
    (the model applied to a part of them) fails the trace loudly: the
    step could not tell what is left for it to reduce."""
    model, _, params = _scanned_lm("full")

    def loss_fn(nested, tokens):
        return model.apply({"params": nested["lm"]}, tokens).mean()

    def fn():
        step = hvd.make_compiled_train_step(loss_fn, optax.sgd(0.1))
        state = step.init_state({"lm": params})
        with pytest.raises(ValueError, match="reduce_in_backward covered"):
            step(state, _rank_tokens(hvd.rank()))
        return True

    assert all(run_ranks(fn, 2))


@pytest.mark.parametrize("op", [hvd.Average, hvd.Sum],
                         ids=["average", "sum"])
def test_reduce_in_backward_in_a_users_own_scan(hvd_shutdown, monkeypatch,
                                                op):
    """``hvd.reduce_in_backward`` on the per-layer slice inside a plain
    ``lax.scan`` under ``jax.checkpoint``: the same update as without
    it, and the layers' bytes counted as reduced in the backward."""
    import jax
    import jax.numpy as jnp

    params = {"layers": {"w": np.linspace(-1, 1, 3 * 8 * 8, dtype=np.float32)
                         .reshape(3, 8, 8)},
              "out": np.ones((8,), np.float32)}

    def loss_fn(p, x):
        @jax.checkpoint
        def layer(h, w):
            w = hvd.reduce_in_backward(w, covers=("layers",))
            return jnp.tanh(h @ w["w"]), None

        h, _ = jax.lax.scan(layer, x, p["layers"])
        return jnp.mean((h @ p["out"]) ** 2)

    def fn():
        step = hvd.make_compiled_train_step(loss_fn, optax.sgd(0.5), op=op)
        state = step.init_state(params)
        before = _reduce_counters()
        x = np.full((4, 8), 0.1 * (hvd.rank() + 1), np.float32)
        state, loss = step(state, x)
        after = _reduce_counters()
        return (float(loss), jax.device_get(state["params"]),
                after[1] - before[1])

    hooked = run_ranks(fn)
    _without_hooks(monkeypatch)
    plain = run_ranks(fn)
    for mine, theirs in zip(hooked, plain):
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-7), mine[:2], theirs[:2])
        assert mine[2] == _tree_bytes(params["layers"]) and theirs[2] == 0
