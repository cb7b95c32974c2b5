"""Expert-parallel token dispatch (parallel/moe.py) + the MoE wiring
around it: deterministic top-k gating, fixed-capacity overflow
accounting, the straight-through combine gradient, the quantized
dispatch wire, the transformer's capacity-routing branch, the
autotuner's tenth dimension, and error-feedback hygiene on the
compiled alltoall.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import horovod_tpu as hvd
from horovod_tpu.parallel import moe


NP = 4


@pytest.fixture(scope="module")
def live_engine():
    if hvd.is_initialized():
        hvd.shutdown()
    hvd.run(lambda: None, np=NP, keep_alive=True)
    yield
    hvd.shutdown()


# ---------------------------------------------------------------------------
# gating + dispatch plan determinism


def test_top_k_gating_deterministic_and_normalized():
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    w1, i1 = moe.top_k_gating(logits, 2)
    w2, i2 = moe.top_k_gating(logits, 2)
    # same logits -> bitwise-same routes and weights (lax.top_k
    # breaks ties on the lowest index; nothing samples)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    assert np.array_equal(np.asarray(w1), np.asarray(w2))
    # weights renormalize over the SELECTED experts
    np.testing.assert_allclose(np.asarray(w1.sum(-1)), 1.0, atol=1e-5)
    # routes are the true top-k of the softmax
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    for t in range(logits.shape[0]):
        top = set(np.argsort(-probs[t])[:2])
        assert set(np.asarray(i1)[t]) == top


def test_dispatch_plan_tie_break_is_token_major():
    # every token wants expert 0; capacity admits the FIRST cap
    # tokens in token order, deterministically
    idx = jnp.zeros((6, 1), jnp.int32)
    pos, keep, dropped = moe.make_dispatch_plan(idx, 4, 4)
    assert np.array_equal(np.asarray(keep).ravel(),
                          [True] * 4 + [False] * 2)
    assert np.array_equal(np.asarray(pos).ravel(), [0, 1, 2, 3, 4, 5])
    assert int(dropped) == 2


def test_capacity_overflow_drop_accounting():
    # 32 tokens x top-1, all routed to expert 0, capacity 5:
    # exactly 27 dropped, and the dispatched slots hold the first 5
    T, E, cap = 32, 4, 5
    logits = np.zeros((T, E), np.float32)
    logits[:, 0] = 10.0
    w, idx = moe.top_k_gating(jnp.asarray(logits), 1)
    pos, keep, dropped = moe.make_dispatch_plan(idx, E, cap)
    assert int(dropped) == T - cap
    x = jnp.asarray(np.arange(T, dtype=np.float32)[:, None])
    slots = moe.moe_dispatch(x, idx, pos, keep, E, cap)
    assert slots.shape == (E, cap, 1)
    np.testing.assert_allclose(
        np.asarray(slots)[0, :, 0], np.arange(cap, dtype=np.float32))
    # dropped tokens contribute zero on the way back too
    y = moe.moe_combine(slots, idx, pos, keep, w)
    np.testing.assert_allclose(np.asarray(y)[cap:], 0.0)


def test_expert_capacity_and_snap_ep():
    # ceil(cf * T * K / E), floored at 1
    assert moe.expert_capacity(128, 8, 2, 1.25) == 40
    assert moe.expert_capacity(1, 64, 1, 1.0) == 1
    # ep snaps to the largest divisor of the world size
    assert moe.snap_ep(8, 8) == 8
    assert moe.snap_ep(8, 6) == 6
    assert moe.snap_ep(3, 8) == 2
    assert moe.snap_ep(0, 4) == 1


def test_moe_label_round_trip():
    for ep, cf in moe.MOE_CHOICES:
        assert moe.parse_moe_label(moe.moe_label(ep, cf)) == (ep, cf)


def test_dense_flop_matched_ff():
    # top-k of d_ff_expert costs K * d_ff_expert dense-equivalent
    assert moe.dense_flop_matched_ff(256, 2) == 512


# ---------------------------------------------------------------------------
# straight-through combine gradient


def test_straight_through_grad_reaches_router():
    w = jnp.asarray([0.6, 0.4], jnp.float32)
    keep = jnp.asarray([True, False])

    def f(w):
        return jnp.sum(moe.straight_through(w, keep) * 2.0)

    # forward masks the dropped choice...
    assert float(f(w)) == pytest.approx(1.2)
    # ...but the backward is identity to w: the router keeps getting
    # gradient for hot (dropped) experts instead of starving
    g = jax.grad(f)(w)
    np.testing.assert_allclose(np.asarray(g), [2.0, 2.0])


# ---------------------------------------------------------------------------
# the quantized dispatch wire (in-graph codec)


def test_quantized_all_to_all_single_device_round_trip():
    # axis of size 1: the exchange is identity, the codec is not —
    # int8 must round-trip within half a quantization step
    x = jnp.asarray(
        np.linspace(-1.0, 1.0, 512, dtype=np.float32).reshape(1, 512))

    def run(v):
        return moe.quantized_all_to_all(v, "x", "int8")

    from horovod_tpu.common.shard_compat import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    out = shard_map(run, mesh=mesh, in_specs=(P("x"),),
                    out_specs=P("x"), check_vma=False)(x)
    err = np.abs(np.asarray(out) - np.asarray(x)).max()
    assert err <= 1.0 / 127.0 + 1e-6, err


def test_quantized_all_to_all_has_custom_vjp():
    # the backward is the same exchange of the cotangent (alltoall is
    # its own transpose); with axis size 1 that means grad == ones
    x = jnp.asarray(np.ones((1, 256), np.float32))

    def loss(v):
        return jnp.sum(moe.quantized_all_to_all(v, "x", None))

    from horovod_tpu.common.shard_compat import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    g = shard_map(jax.grad(loss), mesh=mesh, in_specs=(P("x"),),
                  out_specs=P("x"), check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(g), 1.0)


# ---------------------------------------------------------------------------
# transformer capacity-routing branch


def test_transformer_moe_capacity_branch_runs_and_differs():
    from horovod_tpu.models.transformer import (
        TransformerConfig, TransformerLM)

    def build(cf):
        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=2, n_layers=1,
            d_ff=64, max_seq_len=16, num_experts=4, expert_top_k=2,
            moe_capacity_factor=cf)
        model = TransformerLM(cfg)
        tokens = jnp.asarray(
            np.random.default_rng(3).integers(0, 64, (2, 8)))
        params = model.init(jax.random.PRNGKey(0), tokens)
        return model.apply(params, tokens)

    dense = build(0.0)    # legacy dense one-hot dispatch
    routed = build(4.0)   # capacity so generous nothing drops
    assert np.asarray(routed).shape == np.asarray(dense).shape
    assert np.all(np.isfinite(np.asarray(routed)))


def test_capacity_routed_step_compiles_once():
    """Fixed-capacity dispatch keeps every shape static: batches that
    route (and drop) differently run the one program the first step
    compiled — the count ``tools/perf_gate.py`` reads off lm_bench as
    ``moe_steady_recompiles``."""
    from horovod_tpu.models.transformer import TransformerConfig
    from horovod_tpu.parallel import (
        MeshSpec, build_mesh, make_lm_train_step)

    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=16, num_experts=4, expert_top_k=2,
        moe_capacity_factor=1.25)
    init, _, jit_step, tok_sharding = make_lm_train_step(mesh, cfg)
    rng = np.random.default_rng(3)
    batches = [jnp.asarray(rng.integers(0, 64, (2, 16)))
               for _ in range(4)]
    step, state = jit_step(init(jax.random.PRNGKey(0), batches[0]))
    for tokens in batches:
        state, loss = step(state, jax.device_put(tokens, tok_sharding))
    assert np.isfinite(float(loss))
    assert step._cache_size() == 1


# ---------------------------------------------------------------------------
# autotune: the tenth dimension


def test_autotune_tenth_dimension_encode_decode():
    from horovod_tpu.core.autotune import ParameterManager
    from horovod_tpu.common.env import Config

    cfg = Config()
    cfg.moe_experts = 8
    pm = ParameterManager(cfg, tune_pipeline=True, tune_sharded=True,
                          tune_overlap=True, tune_moe=True)
    # 4 continuous knobs + wire + algorithm + pp + shard + overlap
    # + the MoE (ep, capacity factor) pair = the TENTH dimension
    assert pm._bo.dims == 10
    def enc(pair):
        return pm._encode(64 * 2 ** 20, 1.0, 8 << 20, 1024,
                          (None, None), None, (None, 0), None, 0,
                          moe_pair=pair)

    for ep, cf in ((1, 1.0), (4, 1.25), (8, 1.5)):
        assert pm._decode(enc((ep, cf)))[-1] == (ep, cf)
    # off-grid incumbent seeds the nearest bin of its ep degree
    assert pm._decode(enc((4, 1.3)))[-1] == (4, 1.25)


def test_autotune_without_moe_stays_nine_dims():
    from horovod_tpu.core.autotune import ParameterManager
    from horovod_tpu.common.env import Config

    pm = ParameterManager(Config(), tune_pipeline=True,
                          tune_sharded=True, tune_overlap=True,
                          tune_moe=False)
    assert pm._bo.dims == 9
    assert "|moe" not in pm._key_suffix


# ---------------------------------------------------------------------------
# error-feedback hygiene on the alltoall wire


def test_compiled_alltoall_ef_reset_on_wire_state_reset(live_engine):
    """reset_wire_state must drop the device residuals (stale EF
    after an elastic resize or a quarantine is a divergence bug)."""
    from horovod_tpu.ops import compiled as cm

    def fn():
        a2a = hvd.CompiledAlltoall(name="moe.ef", wire_dtype="int8",
                                   error_feedback=True,
                                   force_program=True)
        x = np.linspace(-1.0, 1.0, NP * 512).astype(np.float32)
        a2a(x)
        keys = set(a2a._ef_keys)
        assert keys and all(k in cm._EF_STATE for k in keys)
        # the residuals are shared by the ranks' equivalent objects:
        # every rank looks before any rank drops them
        hvd.barrier()
        a2a.reset_wire_state()
        assert not a2a._ef_keys
        assert all(k not in cm._EF_STATE for k in keys)
        return True

    assert all(hvd.run(fn, np=NP))


def test_engine_alltoall_ef_dropped_on_layout_change(live_engine):
    """A residual carried across a splits/layout change would re-
    inject against the wrong peer slots — the engine must drop it."""
    from horovod_tpu.common import basics

    def fn():
        eng = basics.engine()
        a = np.linspace(-1.0, 1.0, NP * 512).astype(np.float32)
        hvd.alltoall(a, wire_dtype="int8", name="moe.ef.eng")
        shapes0 = {k: v.shape for k, v in eng._a2a_ef.items()}
        assert shapes0, "no EF residual recorded"
        b = np.linspace(-1.0, 1.0, NP * 1024).astype(np.float32)
        hvd.alltoall(b, wire_dtype="int8", name="moe.ef.eng2")
        # every residual now matches the NEW layout only
        assert all(v.size == b.size
                   for v in eng._a2a_ef.values())
        return True

    assert all(hvd.run(fn, np=NP))


def test_engine_alltoall_ef_off_is_stateless(live_engine):
    def fn():
        from horovod_tpu.common import basics
        eng = basics.engine()
        x = np.linspace(-1.0, 1.0, NP * 512).astype(np.float32)
        o1, _ = hvd.alltoall(x, wire_dtype="int8", name="moe.ef.off",
                             error_feedback=False)
        o2, _ = hvd.alltoall(x, wire_dtype="int8", name="moe.ef.off2",
                             error_feedback=False)
        # stateless encode: identical inputs -> identical outputs,
        # and no residual is carried
        assert np.array_equal(np.asarray(o1), np.asarray(o2))
        assert not eng._a2a_ef
        return True

    assert all(hvd.run(fn, np=NP))
