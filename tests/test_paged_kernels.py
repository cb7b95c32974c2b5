"""The paged decode attention kernel (``ops/paged_kernels.py``) under
the Pallas interpreter against its reference, the XLA form of the decode
tick: ``kvcache._paged_attention`` over the gathered block views.  One
algorithm, one mask, one rounding; the kernel reads the blocks through
the table in place and stops at each slot's own length."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import paged_kernels
from horovod_tpu.serving import kvcache

BT = 4               # tokens a block
LAYERS = 2           # the pools are flat over layers: the kernel is
LAYER = 1            # handed the second layer's base


def xla_form(q, k_pool, v_pool, tables, pos, base, window):
    B, NB = tables.shape
    bt, KV, D = k_pool.shape[1:]
    k = k_pool[tables + base].reshape(B, NB * bt, KV, D)
    v = v_pool[tables + base].reshape(B, NB * bt, KV, D)
    return kvcache._paged_attention(q[:, None], k, v, pos, window)[:, 0]


def kernel_form(q, k_pool, v_pool, tables, pos, base, window, **kw):
    return paged_kernels.paged_decode_attention(
        q, k_pool, v_pool, tables, pos, base, window=window,
        interpret=True, **kw)


def make(pos, width, *, heads=8, kv=2, d=16, dtype=jnp.float32, seed=0,
         tables=None, bt=BT):
    """Pools of random rows and, unless given, each slot's table: its
    blocks drawn from a shuffle of the layer's ids (neither contiguous
    nor ascending), the rest scratch block 0."""
    rng = np.random.default_rng(seed)
    pos = np.asarray(pos, np.int32)
    n_blocks = len(pos) * width + 1
    shape = (LAYERS * n_blocks, bt, kv, d)
    k_pool = jnp.asarray(rng.standard_normal(shape), dtype)
    v_pool = jnp.asarray(rng.standard_normal(shape), dtype)
    q = jnp.asarray(rng.standard_normal((len(pos), heads, d)), dtype)
    if tables is None:
        ids = 1 + rng.permutation(n_blocks - 1)
        tables = np.zeros((len(pos), width), np.int32)
        for b, p in enumerate(pos):
            n = p // bt + 1
            tables[b, :n] = ids[b * width:b * width + n]
    return (q, k_pool, v_pool, jnp.asarray(tables, jnp.int32),
            jnp.asarray(pos), jnp.int32(LAYER * n_blocks))


def agree(got, want, dtype=jnp.float32):
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


WIDEST = 16          # 64 positions of 4-token blocks


@pytest.mark.parametrize("pos", [0, BT - 1, BT, WIDEST * BT - 1],
                         ids=["first", "block_end", "block_start",
                              "table_end"])
@pytest.mark.parametrize("blocks", [1, 4])
def test_a_slot_at_the_edges_of_a_block_and_of_the_table(pos, blocks):
    case = make([pos, pos], WIDEST, seed=pos)
    agree(kernel_form(*case, None, blocks_per_step=blocks),
          xla_form(*case, None))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("heads, kv", [(4, 4), (8, 2), (7, 1), (14, 2)],
                         ids=["g1", "g4", "g7", "g7_kv2"])
def test_slots_of_very_different_lengths_by_group_and_dtype(heads, kv,
                                                            dtype):
    # an inactive slot (position 0, a table of scratch) among them
    pos = [61, 0, 2, 17, 33]
    case = make(pos, WIDEST, heads=heads, kv=kv, dtype=dtype, seed=heads)
    tables = np.array(case[3])
    tables[1] = 0
    case = case[:3] + (jnp.asarray(tables),) + case[4:]
    agree(kernel_form(*case, None), xla_form(*case, None), dtype)


@pytest.mark.parametrize("width", [1, 2, WIDEST])
def test_table_widths_from_one_block_to_the_widest(width):
    pos = np.minimum([width * BT - 1, 1, width * BT // 2], width * BT - 1)
    case = make(pos, width, seed=width)
    agree(kernel_form(*case, None), xla_form(*case, None))


def test_tables_in_any_order_that_share_a_block():
    # descending ids, and two slots that read one block (a shared
    # prefix would): the kernel follows the table, not the pool's order
    tables = [[9, 8, 7, 6], [3, 9, 1, 0], [5, 0, 0, 0]]
    case = make([15, 11, 2], 4, tables=tables)
    agree(kernel_form(*case, None), xla_form(*case, None))


@pytest.mark.parametrize("pos", [46, 47, 48, 49, 63])
@pytest.mark.parametrize("blocks", [2, 8])
def test_a_window_that_binds(pos, blocks):
    # the rehearsal's window of 48 over a table of 64 positions: at 47
    # every key is seen, from 48 on the first ones leave, block by
    # block; three slots so that one starts inside a block
    case = make([pos, pos - 3, 5], WIDEST, seed=pos)
    agree(kernel_form(*case, 48, blocks_per_step=blocks),
          xla_form(*case, 48))


def test_a_window_off_by_one_is_seen():
    case = make([49, 60], WIDEST, seed=3)
    want = np.asarray(xla_form(*case, 48))
    for window in (47, 49):
        gap = np.abs(np.asarray(kernel_form(*case, window)) - want).max()
        assert gap > 1e-3, f"window {window} reads as 48"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_scratch_and_what_lies_past_a_slots_length_are_never_seen(dtype):
    pos = [5, 0, 22]
    q, k_pool, v_pool, tables, posj, base = make(pos, 8, dtype=dtype)
    tables = np.array(tables)
    # entries past a slot's blocks name real blocks full of garbage,
    # slot 1 is inactive on scratch, and scratch itself holds garbage
    spare = int(tables.max()) + 1
    tables[0, 2:] = spare
    tables[2, 6:] = spare + 1
    tables[1] = 0
    tables = jnp.asarray(tables)
    clean = kernel_form(q, k_pool, v_pool, tables, posj, base, None)
    dirty_k, dirty_v = k_pool, v_pool
    for block in (0, spare, spare + 1):
        dirty_k = dirty_k.at[base + block].set(1e4)
        dirty_v = dirty_v.at[base + block].set(-1e4)
    # ... and the rows of a slot's LAST block past its position
    dirty_k = dirty_k.at[base + tables[0, 1], 2:].set(1e4)
    dirty_v = dirty_v.at[base + tables[0, 1], 2:].set(-1e4)
    dirty = kernel_form(q, dirty_k, dirty_v, tables, posj, base, None)
    live = np.array([0, 2])
    np.testing.assert_array_equal(np.asarray(dirty, np.float32)[live],
                                  np.asarray(clean, np.float32)[live])
    agree(np.asarray(clean, np.float32)[live],
          np.asarray(xla_form(q, k_pool, v_pool, tables, posj, base,
                              None), np.float32)[live], dtype)


def test_at_the_served_widths_in_bfloat16():
    # Mistral's heads: 32 over 8 KV heads of 128, blocks of 16
    case = make([40, 0, 255, 129], 16, heads=32, kv=8, d=128, bt=16,
                dtype=jnp.bfloat16)
    agree(kernel_form(*case, None), xla_form(*case, None), jnp.bfloat16)


def test_a_table_no_step_divides_is_refused():
    case = make([3], 6)
    with pytest.raises(ValueError, match="multiple"):
        kernel_form(*case, None, blocks_per_step=4)


@pytest.mark.parametrize("pool, heads, dtype, takes", [
    ((16, 8, 128), 32, jnp.bfloat16, True),      # the served cell's
    ((16, 16, 256), 64, jnp.bfloat16, True),
    ((2, 8, 128), 32, jnp.bfloat16, True),       # 16 rows: one tile
    ((16, 8, 128), 32, jnp.float32, False),      # a float32 cache
    ((16, 8, 64), 32, jnp.bfloat16, False),      # half a lane tile
    ((16, 4, 128), 32, jnp.bfloat16, False),     # heads split a tile
    ((1, 8, 128), 32, jnp.bfloat16, False),      # half a tile of rows
    ((16, 8, 128), 8, jnp.bfloat16, False),      # one query head each
    ((4, 2, 16), 4, jnp.float32, False)])        # the CPU tests' models
def test_which_shapes_mosaic_is_handed(pool, heads, dtype, takes):
    assert paged_kernels.kernel_takes((7, 99) + pool, heads, dtype) \
        is takes


def test_the_rule_keeps_the_xla_form_off_the_tpu_and_obeys_an_override(
        monkeypatch):
    from horovod_tpu.models.transformer import TransformerConfig
    from horovod_tpu.ops import pallas_kernels

    served = TransformerConfig(
        vocab_size=64, d_model=4096, n_layers=2, n_heads=32,
        n_kv_heads=8, d_ff=64, max_seq_len=64, dtype=jnp.bfloat16)
    tiny = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=64, dtype=jnp.float32)
    pool = (2, 24, 16, 8, 128)
    assert jax.default_backend() != "tpu"
    assert kvcache.kernel_interpret(served, pool) is None
    assert kvcache.kernel_interpret(served, pool, True) is True
    assert kvcache.kernel_interpret(tiny, (2, 24, 8, 2, 8), False) is False
    # where the process computes on a TPU (asked THROUGH the module, as
    # the chip-compile tools patch it): by shape alone
    monkeypatch.setattr(pallas_kernels, "default_interpret",
                        lambda: False)
    assert kvcache.kernel_interpret(served, pool) is False
    assert kvcache.kernel_interpret(tiny, (2, 24, 8, 2, 8)) is None
