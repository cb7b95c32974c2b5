"""Continuous-batching serving tests (docs/serving.md "Continuous
batching"): deterministic slot join/leave over the paged KV cache,
zero-leaked-blocks drain accounting, per-token parity between
continuous decode and the unbatched flax generate path, the
prefill/decode split through the shared pipeline executor (f32 wire
token-identical, int8 wire smaller), the zero-steady-state-recompile
contract via the program-cache counters, journal recovery after a
decode-replica kill, the ``after_decodes`` chaos trigger, and the
TTFT/tokens-per-sec SLO signals the autoscaler and fleet controller
read."""

import dataclasses
import functools
import json
import os
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu import telemetry
from horovod_tpu.chaos.inject import FaultInjector, _reset_for_tests
from horovod_tpu.chaos.plan import parse_plan
from horovod_tpu.models.transformer import (
    TransformerConfig, TransformerLM, make_generate_fn,
)
from horovod_tpu.ops.compiled import program_cache_stats
from horovod_tpu.serving.autoscale import (
    AutoscalePolicy, ServingSignals, ServingWindow,
)
from horovod_tpu.serving.continuous import (
    ContinuousBatcher, KVWireTransport, PrefillDecodeSplit,
    read_journal,
)
from horovod_tpu.serving.kvcache import (
    BlocksExhausted, KVBlockPool, PagedKVPrograms, _decode_fwd,
    bucket_for, pack_kv_blocks, pow2_buckets, unpack_kv_blocks,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def clean_injector():
    _reset_for_tests()
    yield
    _reset_for_tests()


# -- shared tiny model (module scope: the compiled programs live in the
# process-wide shared cache, so every test reuses one vocabulary) -----------

@pytest.fixture(scope="module")
def bundle():
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4,
        n_kv_heads=2, d_ff=64, max_seq_len=64, dtype=jnp.float32)
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]
    progs = PagedKVPrograms(cfg, max_slots=3, block_tokens=8,
                            n_blocks=24)
    return cfg, model, params, progs


PROMPTS = [
    [5, 9, 2, 41, 7],
    [11, 3, 3, 60, 22, 8, 19],
    [2, 2, 2, 2],
    [33, 1, 48, 17, 9, 5],
]


# -- buckets + pool accounting ----------------------------------------------

def test_pow2_buckets_and_bucket_for():
    assert pow2_buckets(1) == (1,)
    assert pow2_buckets(5) == (1, 2, 4, 8)
    assert pow2_buckets(8) == (1, 2, 4, 8)
    assert bucket_for(3, (1, 2, 4, 8)) == 4
    assert bucket_for(8, (1, 2, 4, 8)) == 8
    with pytest.raises(ValueError):
        bucket_for(9, (1, 2, 4, 8))
    with pytest.raises(ValueError):
        pow2_buckets(0)


def test_kv_pool_lowest_first_and_loud_accounting():
    pool = KVBlockPool(n_blocks=6, block_tokens=8)
    assert pool.capacity == 5          # block 0 is scratch
    a = pool.alloc(2)
    assert a == [1, 2]                 # lowest ids first
    b = pool.alloc(1)
    assert b == [3]
    assert pool.in_use == 3
    pool.free(a)
    assert pool.alloc(2) == [1, 2]     # reuse, still lowest-first
    with pytest.raises(ValueError, match="double free"):
        pool.free(b + b)
    pool.free(b)
    with pytest.raises(ValueError, match="double free"):
        pool.free(b)
    with pytest.raises(ValueError, match="not allocatable"):
        pool.free([0])                 # scratch is never allocatable
    with pytest.raises(BlocksExhausted):
        pool.alloc(4)                  # only 3 free
    pool.free([1, 2])
    assert pool.in_use == 0


def test_kv_pool_publishes_gauge():
    reg = telemetry.fresh_registry()
    try:
        pool = KVBlockPool(n_blocks=4, block_tokens=8)
        blocks = pool.alloc(2)
        assert reg.get("horovod_kv_blocks_in_use").value() == 2
        pool.free(blocks)
        assert reg.get("horovod_kv_blocks_in_use").value() == 0
    finally:
        telemetry.fresh_registry()


# -- parity: continuous decode vs the unbatched flax generate path ----------

# one field of the model a case, each away from its default: what the
# layer, the embedding and the head compute is models/transformer.py's
# own code around serving's cache, so a served stream is the model's
SERVED_FIELDS = [
    {}, {"rms_norm_eps": 1e-5}, {"rms_norm_eps": 0.5}, {"qk_norm": True},
    {"attention_gate": True}, {"residual_multiplier": 0.3},
    {"attention_multiplier": 0.05}, {"embedding_multiplier": 3.0},
    {"tie_word_embeddings": False},
]


def _served(bundle, fields):
    """``bundle`` with ``fields`` set and every weight moved off its
    initial value (a norm's scale starts at one: a norm or a gate left
    out has to show)."""
    if not fields:
        return bundle
    cfg = dataclasses.replace(bundle[0], **fields)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf * (1.0 + 0.3 * jax.random.normal(key, leaf.shape, leaf.dtype))
        for leaf, key in zip(leaves, keys)])
    return cfg, model, params, PagedKVPrograms(
        cfg, max_slots=3, block_tokens=8, n_blocks=24)


@pytest.mark.parametrize(
    "fields", SERVED_FIELDS,
    ids=lambda f: "-".join(f"{k}={v}" for k, v in f.items()) or "base")
def test_continuous_matches_unbatched_generate(bundle, fields):
    cfg, model, params, progs = _served(bundle, fields)
    gen = make_generate_fn(model, max_new_tokens=6)
    refs = [np.asarray(gen(params, jnp.asarray(
        [p], jnp.int32)))[0].tolist() for p in PROMPTS]
    bat = ContinuousBatcher(params, progs, max_new_tokens=6)
    handles = [bat.submit(p) for p in PROMPTS]
    bat.drain()
    for h, ref in zip(handles, refs):
        assert h.done and h.reason == "len"
        assert h.tokens() == ref
    assert bat.pool.in_use == 0


def test_eos_retires_early(bundle):
    cfg, model, params, progs = bundle
    gen = make_generate_fn(model, max_new_tokens=8)
    ref = np.asarray(gen(params, jnp.asarray(
        [PROMPTS[0]], jnp.int32)))[0].tolist()
    eos = ref[2]                       # force an early stop
    bat = ContinuousBatcher(params, progs, eos_id=eos,
                            max_new_tokens=8)
    h = bat.submit(PROMPTS[0])
    bat.drain()
    assert h.reason == "eos"
    assert h.tokens() == ref[:3]
    assert bat.pool.in_use == 0


# -- slot join/leave determinism --------------------------------------------

def _scripted_run(params, progs, journal):
    """Staggered arrivals with slots joining and leaving mid-flight;
    pure tick-scripted (no wall clock) so two runs are bytewise
    comparable."""
    bat = ContinuousBatcher(params, progs, max_new_tokens=5,
                            journal_path=journal)
    handles = [bat.submit(PROMPTS[0], max_new_tokens=3)]
    bat.tick()
    handles.append(bat.submit(PROMPTS[1], max_new_tokens=7))
    handles.append(bat.submit(PROMPTS[2]))
    bat.tick()
    handles.append(bat.submit(PROMPTS[3], max_new_tokens=4))
    bat.drain()
    bat.stop()
    return [h.tokens() for h in handles]


def test_slot_join_leave_determinism(bundle, tmp_path):
    cfg, model, params, progs = bundle
    j1, j2 = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    toks1 = _scripted_run(params, progs, j1)
    toks2 = _scripted_run(params, progs, j2)
    assert toks1 == toks2
    b1 = open(j1, "rb").read()
    assert b1 == open(j2, "rb").read()     # byte-identical evidence
    events = [json.loads(ln) for ln in b1.splitlines()]
    admits = [e for e in events if e["e"] == "admit"]
    assert len(admits) == 4 and len(
        [e for e in events if e["e"] == "retire"]) == 4
    # 4 arrivals over 3 slots: somebody waited for a leave, and the
    # freed slot was re-assigned (join/leave, not batch-at-once)
    assert admits[3]["slot"] in [a["slot"] for a in admits[:3]]
    # per-slot arithmetic still matches the unbatched reference
    gen = make_generate_fn(model, max_new_tokens=7)
    ref = np.asarray(gen(params, jnp.asarray(
        [PROMPTS[1]], jnp.int32)))[0].tolist()
    assert toks1[1] == ref


def test_block_exhaustion_queues_instead_of_failing(bundle):
    cfg, model, params, progs = bundle
    pool = KVBlockPool(n_blocks=3, block_tokens=8)   # 2 real blocks
    bat = ContinuousBatcher(params, progs, pool=pool,
                            max_new_tokens=8)
    h1 = bat.submit(PROMPTS[0])        # 5 + 8 = 13 tokens, 2 blocks
    h2 = bat.submit(PROMPTS[2])        # must wait for h1's blocks
    bat.tick()
    assert bat.active_slots == 1 and bat.queue_depth == 1
    bat.drain()
    assert h1.done and h2.done and pool.in_use == 0


# -- zero steady-state recompiles -------------------------------------------

def test_zero_steady_state_recompiles(bundle):
    cfg, model, params, progs = bundle
    n = progs.warmup(params)
    assert n == len(progs.prompt_buckets) * 2 + len(
        progs.table_buckets)
    hits0, misses0 = program_cache_stats()
    bat = ContinuousBatcher(params, progs, max_new_tokens=6)
    for p in PROMPTS:
        bat.submit(p)
    bat.drain()
    hits1, misses1 = program_cache_stats()
    assert misses1 == misses0, "steady-state decode recompiled"
    assert hits1 > hits0


# -- journal recovery after a kill ------------------------------------------

def test_journal_recovery_reproduces_streams(bundle, tmp_path):
    cfg, model, params, progs = bundle
    golden = str(tmp_path / "golden.jsonl")
    want = _scripted_run(params, progs, golden)

    cut = str(tmp_path / "cut.jsonl")
    bat = ContinuousBatcher(params, progs, max_new_tokens=5,
                            journal_path=cut)
    bat.submit(PROMPTS[0], max_new_tokens=3)
    bat.tick()
    bat.submit(PROMPTS[1], max_new_tokens=7)
    bat.submit(PROMPTS[2])
    bat.tick()
    # the "kill": drop the batcher mid-flight, torn final write and all
    with open(cut, "a", encoding="utf-8") as fh:
        fh.write('{"e": "tok", "seq": 1, "ti')
    del bat

    unfinished, finished = read_journal(cut)
    assert [e["seq"] for e in unfinished] + \
        [e["seq"] for e in finished]
    bat2 = ContinuousBatcher(params, progs, max_new_tokens=5)
    handles = bat2.resume(unfinished)
    # the 4th arrival never reached the dead replica; the client
    # retries it against the recovered one
    h3 = bat2.submit(PROMPTS[3], max_new_tokens=4)
    bat2.drain()
    got = {e["seq"]: list(e["emitted"]) + handles[i].tokens()[
        len(e["emitted"]):] for i, e in enumerate(unfinished)}
    for e in finished:
        got[e["seq"]] = list(e["emitted"])
    got[3] = h3.tokens()
    assert [got[i] for i in range(4)] == want


def test_resume_skips_exhausted_budget_entries(bundle):
    cfg, model, params, progs = bundle
    bat = ContinuousBatcher(params, progs)
    # the kill landed between the last token's journal line and its
    # retire line: nothing left to decode, the stream is complete
    (h,) = bat.resume([{"seq": 0, "prompt": [5, 9], "max_new": 2,
                        "emitted": [7, 8]}])
    assert h.done and h.tokens() == [7, 8]
    assert not bat.has_work()


def test_submit_validation(bundle):
    cfg, model, params, progs = bundle
    bat = ContinuousBatcher(params, progs, max_new_tokens=4)
    with pytest.raises(ValueError, match="empty prompt"):
        bat.submit([])
    with pytest.raises(ValueError, match="max_seq_len"):
        bat.submit(list(range(40)), max_new_tokens=60)
    bat.stop()
    with pytest.raises(RuntimeError, match="draining"):
        bat.submit(PROMPTS[0])


# -- prefill/decode split through the shared executor -----------------------

def test_split_matches_monolithic(bundle):
    cfg, model, params, progs = bundle
    mono = ContinuousBatcher(params, progs, max_new_tokens=6)
    mono_handles = [mono.submit(p) for p in PROMPTS[:3]]
    mono.drain()

    split = PrefillDecodeSplit(params, progs, wire="f32",
                               max_new_tokens=6)
    handles = [split.submit(p) for p in PROMPTS[:3]]
    split.drain()
    assert [h.tokens() for h in handles] == \
        [h.tokens() for h in mono_handles]
    assert split.transport.hops == 3
    assert split.transport.wire_bytes > 0
    assert split.batcher.pool.in_use == 0


def test_split_int8_wire_is_smaller_and_completes(bundle):
    cfg, model, params, progs = bundle
    f32 = PrefillDecodeSplit(params, progs, wire="f32",
                             max_new_tokens=4)
    f32.submit(PROMPTS[0])
    f32.drain()
    q = PrefillDecodeSplit(params, progs, wire="int8",
                           max_new_tokens=4)
    h = q.submit(PROMPTS[0])
    q.drain()
    assert h.done and len(h.tokens()) == 4
    assert q.transport.wire_bytes < f32.transport.wire_bytes / 2


def test_wire_codec_roundtrip():
    rng = np.random.default_rng(3)
    k = rng.standard_normal((2, 16, 2, 8), np.float32)
    v = rng.standard_normal((2, 16, 2, 8), np.float32)
    msg = pack_kv_blocks(k, v, 11, wire="f32")
    k2, v2, n = unpack_kv_blocks(msg)
    assert n == 11
    np.testing.assert_array_equal(k2, k[:, :11])
    np.testing.assert_array_equal(v2, v[:, :11])
    msg8 = pack_kv_blocks(k, v, 11, wire="int8")
    k8, _v8, _ = unpack_kv_blocks(msg8)
    assert k8.shape == (2, 11, 2, 8)
    assert np.max(np.abs(k8 - k[:, :11])) < 0.05
    with pytest.raises(ValueError, match="kv wire"):
        pack_kv_blocks(k, v, 4, wire="bf16")


def test_wire_transport_refuses_gradient_verbs():
    t = KVWireTransport()
    for verb in (t.send_grad, t.recv_grad):
        with pytest.raises(RuntimeError, match="forward-only"):
            verb(None, 0, 0, 1)
    with pytest.raises(RuntimeError, match="forward-only"):
        t.reduce(None, 0)


def test_paged_programs_reject_moe():
    cfg = TransformerConfig(vocab_size=8, d_model=8, n_layers=1,
                            n_heads=2, d_ff=16, max_seq_len=16,
                            num_experts=4, dtype=jnp.float32)
    with pytest.raises(ValueError, match="dense-MLP"):
        PagedKVPrograms(cfg, max_slots=1, block_tokens=4, n_blocks=4)


@pytest.mark.parametrize("fields", [
    dict(layer_types=("mamba", "full_attention"), mamba_n_heads=2,
         mamba_d_head=4),
    dict(layer_types=("full_attention",) * 2, total_ut_steps=2),
], ids=["mamba", "looped"])
def test_paged_programs_refuse_layer_types(fields):
    """Models the paged cache cannot hold are refused by the field's
    name, not served by programs that cannot be right."""
    cfg = TransformerConfig(vocab_size=8, d_model=8, n_layers=2,
                            n_heads=2, d_ff=16, max_seq_len=16,
                            dtype=jnp.float32, **fields)
    with pytest.raises(ValueError, match="layer_types"):
        PagedKVPrograms(cfg, max_slots=1, block_tokens=4, n_blocks=4)


# -- the decode tick writes and reads the pools in place --------------------
# (docs/serving.md "Continuous batching": the pools are the layer loop's
# carry; as its xs / ys every tick copied both pools three times over)

@pytest.fixture(scope="module")
def deep():
    """Three layers, four slots at different positions, slot 2
    inactive with a table of its own, pools of random rows."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=3, n_heads=4,
        n_kv_heads=2, d_ff=64, max_seq_len=64, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
    progs = PagedKVPrograms(cfg, max_slots=4, block_tokens=4,
                            n_blocks=12)
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    pools = tuple(jax.random.normal(k, progs.pool_shape, jnp.float32)
                  for k in keys)
    tick = dict(
        toks=jnp.asarray([[7], [21], [40], [3]], jnp.int32),
        pos=jnp.asarray([5, 0, 9, 2], jnp.int32),
        tables=jnp.asarray([[3, 7, 0, 0], [1, 0, 0, 0],
                            [5, 6, 2, 0], [9, 0, 0, 0]], jnp.int32),
        active=jnp.asarray([True, True, False, True]))
    return cfg, params, progs, pools, tick


def _decode_fn(cfg, progs):
    return functools.partial(_decode_fwd, cfg=cfg, angles=progs._angles,
                             bt=progs.block_tokens)


def _equations(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("width", [1, 2, 4])
def test_decode_carries_the_pools_through_the_layer_loop(deep, width):
    cfg, params, progs, pools, tick = deep
    L, n_blocks = progs.pool_shape[:2]
    slab = progs.pool_shape[1:]
    # a pool or one layer's slab, in any grouping of the leading axes
    pool_like = {progs.pool_shape, (L * n_blocks,) + slab[1:],
                 slab, (1,) + slab}
    size = int(np.prod(progs.pool_shape))
    closed = jax.make_jaxpr(_decode_fn(cfg, progs))(
        params, *pools, tick["toks"], tick["pos"],
        tick["tables"][:, :width], tick["active"])
    loops = scatters = gathers = 0
    for eqn in _equations(closed.jaxpr):
        name = eqn.primitive.name
        if name == "scan":
            loops += 1
            n_in = eqn.params["num_consts"] + eqn.params["num_carry"]
            through = eqn.invars[n_in:] + \
                eqn.outvars[eqn.params["num_carry"]:]
            assert not [v.aval.shape for v in through
                        if v.aval.shape in pool_like], \
                "a pool rides the layer scan as xs / ys"
        elif name in ("dynamic_slice", "dynamic_update_slice"):
            assert eqn.outvars[0].aval.shape not in pool_like, \
                f"{name} yields a layer's slab or a pool"
        elif name in ("scatter", "gather"):
            whole = eqn.invars[0].aval.size == size
            scatters += whole and name == "scatter"
            gathers += whole and name == "gather"
    # one write and one read a pool a layer, each on the WHOLE pool
    assert (loops, scatters, gathers) == (1, 2, 2)


def _plain_decode_rows(cfg, params, k_pool, v_pool, toks, pos, tables,
                       active, bt):
    """The tick layer by layer in a Python loop, each layer's slab
    taken as a value: the roped key and the value every layer caches
    for every slot, ``(L, B, KV, D)`` each."""
    lp = params["layers"]
    B, NB = tables.shape
    half = cfg.head_dim // 2
    freq = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def norm(x, scale):
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * scale

    def rope(x):
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    blk = jnp.where(active, tables[jnp.arange(B), pos // bt], 0)
    off = jnp.where(active, pos % bt, 0)
    seen = jnp.arange(NB * bt)[None, :] <= pos[:, None]
    x = params["embed"][toks[:, 0]]
    groups = cfg.n_heads // cfg.kv_heads
    ks, vs = [], []
    for l in range(cfg.n_layers):
        h = norm(x, lp["ln_attn"]["scale"][l])
        q = rope(jnp.einsum("bm,mhd->bhd", h,
                            lp["attn"]["wq"]["kernel"][l]))
        k = rope(jnp.einsum("bm,mkd->bkd", h,
                            lp["attn"]["wk"]["kernel"][l]))
        v = jnp.einsum("bm,mkd->bkd", h, lp["attn"]["wv"]["kernel"][l])
        ks.append(k)
        vs.append(v)
        kl = k_pool[l].at[blk, off].set(k)[tables].reshape(
            B, NB * bt, cfg.kv_heads, cfg.head_dim)
        vl = v_pool[l].at[blk, off].set(v)[tables].reshape(
            B, NB * bt, cfg.kv_heads, cfg.head_dim)
        qg = q.reshape(B, cfg.kv_heads, groups, cfg.head_dim)
        scores = jnp.einsum("bkgd,bskd->bkgs", qg, kl) / \
            cfg.head_dim ** 0.5
        probs = jax.nn.softmax(
            jnp.where(seen[:, None, None, :], scores, -1e30), axis=-1)
        o = jnp.einsum("bkgs,bskd->bkgd", probs, vl).reshape(
            B, cfg.n_heads, cfg.head_dim)
        x = x + jnp.einsum("bhd,hdm->bm", o,
                           lp["attn"]["wo"]["kernel"][l])
        h2 = norm(x, lp["ln_mlp"]["scale"][l])
        gate = jax.nn.silu(h2 @ lp["mlp"]["wi_gate"]["kernel"][l])
        x = x + (gate * (h2 @ lp["mlp"]["wi_up"]["kernel"][l])) @ \
            lp["mlp"]["wo"]["kernel"][l]
    return jnp.stack(ks), jnp.stack(vs)


def test_decode_writes_each_layers_rows_and_nothing_else(deep):
    cfg, params, progs, pools, tick = deep
    bt = progs.block_tokens
    args = (tick["toks"], tick["pos"], tick["tables"], tick["active"])
    _, k_new, v_new = jax.jit(_decode_fn(cfg, progs))(
        params, *pools, *args)
    k_rows, v_rows = jax.jit(functools.partial(
        _plain_decode_rows, cfg, bt=bt))(params, *pools, *args)
    pos, tables = np.asarray(tick["pos"]), np.asarray(tick["tables"])
    active = np.asarray(tick["active"])
    live, (idle,) = np.flatnonzero(active), np.flatnonzero(~active)
    blk, off = tables[live, pos[live] // bt], pos[live] % bt
    written = np.zeros(progs.pool_shape[:3], bool)
    written[:, blk, off] = True
    for old, new, rows in ((pools[0], k_new, k_rows),
                           (pools[1], v_new, v_rows)):
        old, new = np.asarray(old), np.asarray(new)
        assert new.shape == progs.pool_shape
        changed = (old != new).any(axis=(-2, -1))
        # the inactive slot's row lands in scratch block 0, row 0
        assert not changed[:, 0, 1:].any()
        assert (changed[:, 1:] == written[:, 1:]).all()
        np.testing.assert_allclose(
            new[:, blk, off], np.asarray(rows)[:, live],
            rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            new[:, 0, 0], np.asarray(rows)[:, idle], rtol=1e-5,
            atol=1e-5)


# -- the tick's attention has two forms, one answer -------------------------
# (ops/paged_kernels.py: the Pallas kernel reads each slot's blocks through
# its table in place; the XLA form over gathered views is its reference)

@pytest.fixture(scope="module")
def in_place(bundle):
    """The bundle's programs with the kernel form forced through the
    interpreter."""
    cfg = bundle[0]
    return PagedKVPrograms(cfg, max_slots=3, block_tokens=8,
                           n_blocks=24, interpret=True)


def _serve(params, progs, budgets=(6, 11, 3, 9, 14, 7)):
    """A few mixed requests, more than the slots hold: streams join
    and leave while others decode."""
    bat = ContinuousBatcher(params, progs, max_new_tokens=6)
    prompts = PROMPTS + [list(range(1, 20)), [7] * 9]
    handles = [bat.submit(p, n) for p, n in zip(prompts, budgets)]
    bat.drain()
    assert bat.pool.in_use == 0
    return [h.tokens() for h in handles], bat.k_pool, bat.v_pool


def test_kernel_form_emits_the_xla_forms_tokens_and_leaves_its_pools(
        bundle, in_place):
    _, _, params, progs = bundle
    assert in_place.reads_in_place and not progs.reads_in_place
    kernel0 = telemetry.counter_total(
        "horovod_serve_paged_kernel_ticks_total")
    want, k_want, v_want = _serve(params, progs)
    assert telemetry.counter_total(
        "horovod_serve_paged_kernel_ticks_total") == kernel0
    ticks0 = telemetry.counter_total("horovod_serve_decode_ticks_total")
    got, k_got, v_got = _serve(params, in_place)
    assert got == want
    np.testing.assert_allclose(k_got, k_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(v_got, v_want, rtol=1e-5, atol=1e-5)
    ticks = telemetry.counter_total(
        "horovod_serve_decode_ticks_total") - ticks0
    assert ticks > 0 and telemetry.counter_total(
        "horovod_serve_paged_kernel_ticks_total") - kernel0 == ticks


def test_off_the_tpu_the_tick_takes_the_xla_form_and_says_so(bundle):
    cfg, _, params, progs = bundle
    assert jax.default_backend() != "tpu"
    assert not progs.reads_in_place
    ticks0 = telemetry.counter_total("horovod_serve_decode_ticks_total")
    kernel0 = telemetry.counter_total(
        "horovod_serve_paged_kernel_ticks_total")
    bat = ContinuousBatcher(params, progs, max_new_tokens=4)
    bat.submit(PROMPTS[0])
    bat.drain()
    # the first token is the prefill's: three ticks decode the rest
    assert telemetry.counter_total(
        "horovod_serve_decode_ticks_total") - ticks0 == 3
    assert telemetry.counter_total(
        "horovod_serve_paged_kernel_ticks_total") == kernel0
    # both counters exist from the first tick on (the benchmark's
    # reader tells a zero from a commit without the kernel by that)
    assert telemetry.registry().get(
        "horovod_serve_paged_kernel_ticks_total") is not None


@pytest.mark.parametrize("interpret, gathers, kernels", [
    (None, 2, 0), (True, 0, 1)])
def test_the_kernel_form_gathers_no_view(deep, interpret, gathers,
                                         kernels):
    cfg, params, progs, pools, tick = deep
    size = int(np.prod(progs.pool_shape))
    closed = jax.make_jaxpr(functools.partial(
        _decode_fn(cfg, progs), interpret=interpret))(
        params, *pools, tick["toks"], tick["pos"], tick["tables"],
        tick["active"])
    found = {"gather": 0, "scatter": 0, "pallas_call": 0}
    for eqn in _equations(closed.jaxpr):
        name = eqn.primitive.name
        if name == "pallas_call":
            found[name] += 1
            # both pools whole, as the loop carries them
            assert sum(v.aval.size == size for v in eqn.invars) == 2
        elif name in ("gather", "scatter") \
                and eqn.invars[0].aval.size == size:
            found[name] += 1
    assert found == {"gather": gathers, "scatter": 2,
                     "pallas_call": kernels}


def test_the_two_forms_agree_on_a_tick_with_an_idle_slot(deep):
    cfg, params, progs, pools, tick = deep
    args = (tick["toks"], tick["pos"], tick["tables"], tick["active"])
    want = jax.jit(_decode_fn(cfg, progs))(params, *pools, *args)
    got = jax.jit(functools.partial(
        _decode_fn(cfg, progs), interpret=True))(params, *pools, *args)
    live = np.asarray(tick["active"])
    np.testing.assert_array_equal(np.asarray(got[0])[live],
                                  np.asarray(want[0])[live])
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


# -- chaos: the after_decodes trigger ---------------------------------------

def test_after_decodes_is_its_own_deterministic_counter(
        clean_injector):
    doc = {"seed": 21, "events": [
        {"kind": "delay_ms", "ms": 1, "after_decodes": 3, "count": 2},
        {"kind": "http_error", "code": 503, "after_predicts": 1},
    ]}
    logs = []
    for _run in range(2):
        inj = FaultInjector(parse_plan(doc))
        acts = [inj.before_decode() for _ in range(6)]
        assert [a[0] if a else None for a in acts] == \
            [None, None, "delay", "delay", None, None]
        # predict traffic does not advance the decode counter
        assert inj.before_predict("/predict")[0] == "error"
        logs.append(inj.fired)
    assert logs[0] == logs[1]
    assert [(f["kind"], f["trigger"], f["n"])
            for f in logs[0]][:2] == \
        [("delay_ms", "decodes", 3), ("delay_ms", "decodes", 4)]


def test_chaos_delay_rides_the_decode_tick(bundle, clean_injector):
    from horovod_tpu import chaos

    cfg, model, params, progs = bundle
    chaos.install(parse_plan({"seed": 4, "events": [
        {"kind": "delay_ms", "ms": 1, "after_decodes": 2}]}))
    bat = ContinuousBatcher(params, progs, max_new_tokens=4)
    h = bat.submit(PROMPTS[0])
    bat.drain()
    assert h.done
    assert [f["trigger"] for f in chaos.current().fired] == ["decodes"]


# -- SLO signals: TTFT + tokens/sec -----------------------------------------

def test_serving_window_unpacks_as_legacy_tuple():
    w = ServingWindow(0.2, 5.0, True, ttft_p99_s=0.05,
                      tokens_per_s=12.0, seen_continuous=True)
    p99, queue, seen = w
    assert (p99, queue, seen) == (0.2, 5.0, True)
    assert w.p99_s == 0.2 and w.ttft_p99_s == 0.05
    assert w.tokens_per_s == 12.0 and w.seen_continuous


def test_policy_ttft_slo_breach_and_idle_gate():
    pol = AutoscalePolicy(slo_p99_ms=1000.0, queue_high=100,
                          breach_evals=2, idle_evals=2,
                          cooldown_s=0.0, slo_ttft_ms=100.0)
    # request p99 healthy, TTFT breached -> scale up
    assert pol.decide(0.01, 0, 4, now=1.0, ttft_p99_s=0.5) == 4
    assert pol.decide(0.01, 0, 4, now=2.0, ttft_p99_s=0.5) == 5
    # TTFT over the idle fraction blocks scale-down
    pol2 = AutoscalePolicy(slo_p99_ms=1000.0, idle_evals=2,
                           cooldown_s=0.0, slo_ttft_ms=100.0)
    assert pol2.decide(0.01, 0, 4, now=1.0, ttft_p99_s=0.09) == 4
    assert pol2.decide(0.01, 0, 4, now=2.0, ttft_p99_s=0.09) == 4
    # TTFT healthy -> the idle streak completes
    pol3 = AutoscalePolicy(slo_p99_ms=1000.0, idle_evals=2,
                           cooldown_s=0.0, slo_ttft_ms=100.0)
    assert pol3.decide(0.01, 0, 4, now=1.0, ttft_p99_s=0.001) == 4
    assert pol3.decide(0.01, 0, 4, now=2.0, ttft_p99_s=0.001) == 3


class _EmptyStore(dict):
    def scope(self, prefix):
        return {}


def _payload(lat, ttft, tokens, queue, bounds):
    return {"replica0": {
        ServingSignals.LATENCY_FAMILY: {
            "type": "histogram", "buckets": bounds,
            "samples": [{"counts": lat}]},
        ServingSignals.TTFT_FAMILY: {
            "type": "histogram", "buckets": bounds,
            "samples": [{"counts": ttft}]},
        ServingSignals.TOKENS_FAMILY: {
            "type": "counter", "samples": [{"value": tokens}]},
        ServingSignals.QUEUE_FAMILY: {
            "type": "gauge", "samples": [{"value": queue}]},
    }}


def test_signals_read_ttft_and_token_rate():
    sig = ServingSignals(_EmptyStore())
    bounds = [0.01, 0.1, 1.0]
    w1 = sig.read(_payload([5, 0, 0, 0], [5, 0, 0, 0], 100, 3,
                           bounds))
    assert w1.seen_continuous and w1.seen_serving
    assert w1.tokens_per_s == 0.0          # first read: no baseline
    import time
    time.sleep(0.02)
    w2 = sig.read(_payload([5, 0, 0, 0], [0, 5, 0, 0], 160, 7,
                           bounds))
    assert w2.queue_depth == 7.0
    assert w2.tokens_per_s > 0.0           # 60 tokens this window
    # the TTFT window is the DELTA: all 5 new obs in (0.01, 0.1]
    assert 0.01 <= w2.ttft_p99_s <= 0.1
    # lifetime latency counts unchanged -> empty request window
    assert w2.p99_s is None


def test_signals_without_continuous_families_stay_legacy():
    sig = ServingSignals(_EmptyStore())
    bounds = [0.01, 0.1]
    payload = {"r0": {
        ServingSignals.LATENCY_FAMILY: {
            "type": "histogram", "buckets": bounds,
            "samples": [{"counts": [3, 1, 0]}]},
        ServingSignals.QUEUE_FAMILY: {
            "type": "gauge", "samples": [{"value": 2}]},
    }}
    w = sig.read(payload)
    p99, queue, seen = w
    assert seen and queue == 2.0 and p99 is not None
    assert not w.seen_continuous and w.ttft_p99_s is None


# -- config knobs -----------------------------------------------------------

def test_serving_config_continuous_knobs(monkeypatch):
    from horovod_tpu.serving.replica import ServingConfig

    cfg = ServingConfig()
    assert (cfg.kv_block_tokens, cfg.kv_blocks, cfg.kv_wire) == \
        (16, 256, "f32")
    assert (cfg.decode_slots, cfg.decode_max_tokens) == (8, 64)
    assert cfg.slo_ttft_ms == 500.0 and cfg.slo_tokens_per_s == 0.0
    monkeypatch.setenv("HOROVOD_SERVING_KV_BLOCK_TOKENS", "32")
    monkeypatch.setenv("HOROVOD_SERVING_KV_WIRE", "int8")
    monkeypatch.setenv("HOROVOD_SERVING_DECODE_SLOTS", "16")
    monkeypatch.setenv("HOROVOD_SERVING_SLO_TTFT_MS", "250")
    cfg = ServingConfig()
    assert cfg.kv_block_tokens == 32 and cfg.kv_wire == "int8"
    assert cfg.decode_slots == 16 and cfg.slo_ttft_ms == 250.0
    assert ServingConfig(kv_wire="int4").kv_wire == "int4"


# -- HTTP /generate streaming -----------------------------------------------

class _StubReplica:
    draining = False

    class batcher:
        buckets = (1,)
        max_batch_size = 1
        max_latency_s = 0.01

        @staticmethod
        def queue_depth():
            return 0


def test_frontend_generate_streams_ndjson(bundle):
    from horovod_tpu.serving.frontend import ServingFrontend

    cfg, model, params, progs = bundle
    bat = ContinuousBatcher(params, progs, max_new_tokens=4)
    bat.start()
    fe = ServingFrontend(_StubReplica(), port=0, generator=bat)
    try:
        port = fe.start()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({"tokens": PROMPTS[0],
                             "max_new_tokens": 3}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            lines = [json.loads(ln) for ln in
                     resp.read().decode().splitlines()]
        assert [ln["token"] for ln in lines[:-1]] == \
            lines[-1]["tokens"]
        assert lines[-1]["done"] and lines[-1]["reason"] == "len"
        assert len(lines[-1]["tokens"]) == 3
        stats = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/stats", timeout=10).read())
        assert stats["kv_blocks_in_use"] == 0
        bad = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=b'{"tokens": "nope"}',
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(bad, timeout=10)
        assert err.value.code == 400
    finally:
        fe.stop()
        bat.stop()


# -- end-to-end smoke (parity + kill drill; ci.sh serve runs it) ------------

@pytest.mark.integration
@pytest.mark.slow
def test_continuous_smoke_end_to_end():
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "continuous_smoke.py")],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": REPO})
    assert proc.returncode == 0, (proc.stdout[-2000:],
                                  proc.stderr[-3000:])
    assert "CONTINUOUS SMOKE OK" in proc.stdout
