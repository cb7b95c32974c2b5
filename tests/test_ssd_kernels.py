"""The selective scan's kernel pair (``ops/ssd_kernels.py``) in the
interpreter: against ``ssd_chunked`` and against the token-by-token
recurrence in value and in every gradient, the state crossing a chunk
boundary, the choice between kernels and the XLA form by shape alone,
and what a remat replay and the step program's own text hold of them."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu import telemetry
from horovod_tpu.models import (TransformerConfig, TransformerLM,
                                make_fused_lm_loss)
from horovod_tpu.models import mamba
from horovod_tpu.models.mamba import (SSM_DEVICE_SUMS, Mamba2Mixer,
                                      ssd_chunked)
from horovod_tpu.ops import ssd_kernels
from horovod_tpu.ops.ssd_kernels import kernel_takes, ssd_scan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench.references import granite_hybrid_train as reference  # noqa: E402
from chipbench.references import precision  # noqa: E402

EINSUM, _ = precision.products("float32")
F32 = jnp.float32


def _inputs(seq, groups=1, rows=2, heads=4, width=8, state=16, seed=0,
            dtype=F32):
    """Inputs in Mamba-2's own regime (dt log-uniform in [0.001, 0.1], A
    in [1, 16]: a state outlives many chunks), a skip, and a cotangent."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (rows, seq, heads, width), F32)
    dt = jnp.exp(jax.random.uniform(ks[1], (rows, seq, heads), F32,
                                    np.log(1e-3), np.log(1e-1)))
    a = -jax.random.uniform(ks[2], (heads,), F32, 1.0, 16.0)
    b = jax.random.normal(ks[3], (rows, seq, groups, state), F32)
    c = jax.random.normal(ks[4], (rows, seq, groups, state), F32)
    skip = jax.random.normal(ks[5], (heads,), F32)
    ct = jax.random.normal(ks[6], (rows, seq, heads, width), F32)
    return (x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype),
            skip), ct


def _recurrence(x, dt, a, b, c, skip):
    y = jax.vmap(lambda x, dt, b, c: reference.selective_scan(
        EINSUM, x, dt, a, b, c, block=8))(x, dt, b, c)
    return y + skip[:, None] * x


def _xla(chunk):
    def fn(x, dt, a, b, c, skip):
        y, _ = ssd_chunked(x, dt, a, b, c, chunk=chunk)
        return (y + skip[:, None] * x.astype(F32)).astype(x.dtype)
    return fn


def _kernels(chunk, block_heads):
    def fn(*args):
        return ssd_scan(*args, chunk=chunk, block_heads=block_heads,
                        interpret=True)[0]
    return fn


def _through(fn, inputs, ct):
    return jax.jit(jax.value_and_grad(
        lambda *args: jnp.sum(fn(*args).astype(F32) * ct),
        argnums=tuple(range(6))))(*inputs)


def _gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("seq, chunk, groups, rows, block_heads", [
    (64, 64, 1, 1, 4),     # one chunk: no state is carried
    (64, 16, 1, 1, 2),     # several chunks, two blocks of heads
    (50, 16, 1, 1, 4),     # the row ends inside a chunk: filled up
    (12, 16, 1, 2, 2),     # rows shorter than one chunk
    (48, 16, 1, 2, 4),     # two rows: the state starts anew in each
    (40, 8, 2, 2, 2),      # two groups of B and C, a block a group
    (40, 8, 2, 1, 1),      # ... and two blocks a group
])
def test_kernel_pair_is_the_xla_form_and_the_recurrence(
        seq, chunk, groups, rows, block_heads, dtype):
    """Value and every gradient (x, dt, a, b, c and the skip).  float32:
    the three differ by the order of float32 sums.  bfloat16: the
    kernels cast where ``ssd_chunked`` casts, or at fewer places (the
    cotangents of its products stay float32), so each is held to the
    float32 recurrence, and the kernels no further from it than the XLA
    form is (with room for where a rounding falls)."""
    inputs, ct = _inputs(seq, groups, rows, dtype=dtype)
    got, got_grads = _through(_kernels(chunk, block_heads), inputs, ct)
    xla, xla_grads = _through(_xla(chunk), inputs, ct)
    exact = tuple(t.astype(F32) for t in inputs)
    want, want_grads = _through(_recurrence, exact, ct)
    if dtype == jnp.float32:
        assert float(got) == pytest.approx(float(want), rel=1e-5, abs=1e-4)
        assert float(got) == pytest.approx(float(xla), rel=1e-5, abs=1e-4)
        for g, x, w in zip(got_grads, xla_grads, want_grads):
            assert _gap(g, w) < 5e-5 and _gap(g, x) < 5e-5
    else:
        assert float(got) == pytest.approx(float(want), rel=2e-2, abs=0.5)
        for g, x, w in zip(got_grads, xla_grads, want_grads):
            assert _gap(g, w) < max(2e-2, 1.5 * _gap(x, w))
    assert ssd_scan(*inputs, chunk=chunk, block_heads=block_heads,
                    interpret=True)[1] == -(-seq // min(chunk, seq))


@pytest.mark.parametrize("block_heads", [2, 4])
def test_the_state_carried_across_chunk_boundaries_matters(block_heads):
    """The planted fault of PR 39: every chunk run alone from a zero
    state (a sweep that does not carry) equals the recurrence inside the
    first chunk and is far from it after, in value and in the gradient
    that flows back across the boundary; the kernels' sweep is the
    recurrence."""
    inputs, ct = _inputs(64, rows=1)
    inputs = inputs[:5] + (jnp.zeros_like(inputs[5]),)    # the scan alone
    scan = _kernels(16, block_heads)

    def alone(x, dt, a, b, c, skip):
        split = lambda t: t.reshape((4, 16) + t.shape[2:])  # noqa: E731
        return scan(split(x), split(dt), a, split(b), split(c),
                    skip).reshape(x.shape)

    whole, whole_grads = _through(scan, inputs, ct)
    want, want_grads = _through(_recurrence, inputs, ct)
    assert _gap(scan(*inputs), _recurrence(*inputs)) < 2e-5
    assert _gap(whole_grads[0], want_grads[0]) < 5e-5
    cut = alone(*inputs)
    assert _gap(cut[:, :16], scan(*inputs)[:, :16]) < 1e-6
    far = jnp.abs(cut[:, 16:] - _recurrence(*inputs)[:, 16:])
    assert float(far.mean()) > 0.1 * float(
        jnp.abs(_recurrence(*inputs)[:, 16:]).mean())
    _, cut_grads = _through(alone, inputs, ct)
    # x of the first chunk feeds every later one through the state
    assert _gap(cut_grads[0][:, :16], want_grads[0][:, :16]) > 0.05


@pytest.mark.parametrize("shape, takes", [
    # (seq, heads, width, groups, state, chunk, dtype)
    ((8192, 64, 64, 1, 128, 256, jnp.bfloat16), True),    # Granite's cell
    ((128, 64, 64, 1, 128, 256, jnp.bfloat16), True),     # its rehearsal
    ((8192, 64, 64, 1, 128, 256, jnp.float32), True),
    ((256, 16, 64, 2, 128, 128, jnp.bfloat16), True),     # 8 heads a group
    ((256, 8, 64, 2, 128, 128, jnp.bfloat16), False),     # 4 heads a group
    ((32, 4, 16, 1, 8, 8, jnp.float32), False),           # the tests' model
    ((8192, 64, 64, 1, 128, 64, jnp.bfloat16), False),    # a chunk of 64
    ((8192, 64, 64, 1, 64, 256, jnp.bfloat16), False),    # a state of 64
    ((8192, 64, 4, 1, 128, 256, jnp.bfloat16), False),    # heads of 4
    ((8192, 64, 64, 1, 128, 256, jnp.float16), False),
])
def test_the_choice_is_by_shape_and_dtype_alone(shape, takes):
    seq, heads, width, groups, state, chunk, dtype = shape
    assert kernel_takes((1, seq, heads, width), (1, seq, groups, state),
                        chunk, dtype) is takes


def _mixer_config(**changes):
    """A mixer whose scan fills whole tiles: 8 heads of 16, a state of
    128, chunks of 128."""
    return TransformerConfig(**{**dict(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
        head_dim=16, d_ff=48, max_seq_len=256,
        rope_on_full_attention=False,
        layer_types=("mamba", "mamba"), mamba_n_heads=8,
        mamba_d_head=16, mamba_d_state=128, mamba_n_groups=1,
        mamba_chunk_size=128, dtype=jnp.float32), **changes})


@pytest.mark.parametrize("seq", [256, 200])
def test_both_paths_of_the_mixer_agree_and_count_alike(monkeypatch, seq):
    """The same weights through the kernel pair and (the choice steered
    here, in the test) through the XLA form: one output, one gradient,
    the same tokens and chunks; the kernel-chunks count tells them
    apart."""
    mixer = Mamba2Mixer(_mixer_config())
    h = jax.random.normal(jax.random.PRNGKey(1), (2, seq, 32), F32)
    params = mixer.init(jax.random.PRNGKey(2), h)

    def run():
        def loss(p):
            out, counts = mixer.apply(p, h)
            return jnp.sum(out ** 2), counts
        (value, counts), grads = jax.value_and_grad(loss, has_aux=True)(
            params)
        return value, counts.tolist(), grads

    value, counts, grads = run()
    monkeypatch.setattr(ssd_kernels, "kernel_takes", lambda *a: False)
    xla_value, xla_counts, xla_grads = run()
    chunks = 2 * -(-seq // 128)
    assert counts == [2 * seq, chunks, chunks]
    assert xla_counts == [2 * seq, chunks, 0]
    assert float(value) == pytest.approx(float(xla_value), rel=1e-5)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(xla_grads)):
        assert _gap(g, w) < 5e-5


def _scan_calls(**changes):
    """``(ssd_fwd, ssd_bwd)`` calls in the gradient of a two-layer
    model's jaxpr (the layers are ONE scan's body)."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 256), 0, 64)
    model = TransformerLM(_mixer_config(**changes))
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: model.apply({"params": p}, tokens).sum()))(params))
    return tuple(len(re.findall(rf"name={name}\b", text))
                 for name in ("ssd_fwd", "ssd_bwd"))


@pytest.mark.parametrize("policy", ["full", "dots"])
def test_a_remat_replay_runs_no_scan(policy):
    """The rule's residuals are the scan's inputs and the states its
    chunks start from, which every policy keeps by name beside the
    scan's output: the gradient of a rematerialised model holds one
    ``ssd_fwd`` and one ``ssd_bwd`` a mamba layer (here: in the body of
    the scan over the two layers) and no second forward.  Without remat
    the same; with the name NOT kept the replay runs the forward again
    (the next test: the count can tell)."""
    assert _scan_calls(remat=True, remat_policy=policy) == (1, 1)
    assert _scan_calls(remat=False) == (1, 1)


def test_a_replay_without_the_kept_states_runs_the_forward_again(
        monkeypatch):
    from horovod_tpu.models import transformer

    monkeypatch.setattr(transformer, "SSD_KEPT", (mamba.KEPT_OUTPUT,))
    assert _scan_calls(remat=True) == (2, 1)


def test_full_remat_keeps_the_scans_outputs_and_states_alone(capsys):
    """Beside every layer's input: each scan's output (d_inner wide) and
    the states its chunks start from (heads x width lanes of float32, a
    state's rows), by their names, and no other value of a layer."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 256), 0, 64)
    model = TransformerLM(_mixer_config(remat=True))
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    jax.ad_checkpoint.print_saved_residuals(
        lambda p: model.apply({"params": p}, tokens).sum(), params)
    kept = [line.split(" ")[0] for line in capsys.readouterr().out.splitlines()
            if "output of scan" in line or "named '" in line]
    shapes = sorted(kept)
    assert shapes.count("f32[2,1,256,128]") == 1           # scans' outputs
    assert shapes.count("f32[2,1,2,128,128]") == 1         # chunk states
    assert len([s for s in shapes if s.startswith("f32[2,1,256,32]")]) >= 1


def test_the_step_program_books_the_kernels_under_the_scan(hvd_shutdown):
    """What ``ssm_scan_ms_per_step`` and ``ssm_scan_roofline`` read: in
    the step program's text ``ssd_fwd`` sits under ``mamba`` + ``ssd``
    in the forward, ``ssd_bwd`` under them inside ``transpose(``, and
    the replay (``rematted_computation``, which would read ``remat``)
    holds neither; the counters say that every chunk went through the
    kernels."""
    from chipbench import scope_join, scope_time

    hvd.init(num_ranks=1)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (1, 256),
                                           0, 64))
    model = TransformerLM(_mixer_config(remat=True))
    loss_fn = make_fused_lm_loss(model, n_chunks=2)
    assert loss_fn.device_sums == SSM_DEVICE_SUMS
    params = jax.device_get(model.init(
        jax.random.PRNGKey(0), jnp.asarray(tokens))["params"])
    step = hvd.make_compiled_train_step(loss_fn, optax.adamw(1e-3))
    state = step.init_state(params)
    before = [telemetry.counter_total(n) for n in SSM_DEVICE_SUMS]
    state, loss = step(state, tokens)
    assert np.isfinite(float(loss))
    # the interpreter inlines a kernel's body: every operation of it
    # carries the kernel's scope, as the chip's one custom call does (a
    # fusion the CPU compiler made of several is read by its step path,
    # as the benchmark reads it)
    paths = {scope_join.step_path(p)
             for p in step.report()["scopes"].values() if p}
    paths = {p for p in paths if re.search(r"hvd_step/.*ssd_(fwd|bwd)", p)}
    scan = re.compile(scope_time.under("mamba", "ssd"))
    fwd = {p for p in paths if "ssd_fwd" in p}
    bwd = {p for p in paths if "ssd_bwd" in p}
    assert fwd and bwd
    assert all(scan.search(p) for p in fwd | bwd)
    assert {scope_join.phase_of(p) for p in fwd} == {"forward"}
    assert {scope_join.phase_of(p) for p in bwd} == {"backward"}
    delta = [telemetry.counter_total(n) - b
             for n, b in zip(SSM_DEVICE_SUMS, before)]
    assert delta == [256 * 2, 2 * 2, 2 * 2]


def test_the_kernels_skip_is_the_mixers(monkeypatch):
    """``D`` is added inside the forward kernel and its gradient comes
    from the rule: a model that trains through the kernels moves ``D``
    as the XLA form does."""
    mixer = Mamba2Mixer(_mixer_config())
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 128, 32), F32)
    params = mixer.init(jax.random.PRNGKey(4), h)
    grad = jax.grad(lambda p: jnp.sum(mixer.apply(p, h)[0] ** 2))
    got = grad(params)["params"]["D"]
    monkeypatch.setattr(ssd_kernels, "kernel_takes", lambda *a: False)
    want = grad(params)["params"]["D"]
    assert float(jnp.abs(want).max()) > 0 and _gap(got, want) < 5e-5
