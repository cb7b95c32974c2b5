"""Decode attention over the paged KV cache, read in place
(``serving/kvcache.py``): one Pallas kernel that follows each slot's
block table through the pools where they lie, up to the slot's own
length, and never materialises a gathered view.

The XLA form (``kvcache._paged_attention`` over ``pool[tables]``)
gathers ``slots x table width`` blocks into a dense array for keys and
again for values, every layer, and then scores all of it: every slot
pays for the widest table among the active slots.  Here:

* the two pools stay in HBM as the decode tick carries them, flat
  ``(L x n_blocks, block_tokens, KV, D)``; ``tables (B, NB)``, ``pos
  (B,)`` and the layer's ``base`` (its first block in the flat pool) are
  scalar prefetch;
* a grid step is one slot.  It visits the table entries that hold keys
  the slot may attend (``pos // bt`` and before; with a window only back
  to the block of ``pos - window + 1``), ``blocks_per_step`` at a time:
  each block is one ``make_async_copy`` of a contiguous ``(bt, KV, D)``
  slab into one of two VMEM buffers, the next step's copies in flight
  while this step is scored, across slots too: a slot's last step
  starts the next slot's first copies, so only the first slot waits
  for its blocks.  The loop's length is read from ``pos``, so the
  copies follow the rows that exist, not the table's width;
* a step scores ALL query heads against ALL of its rows in one product,
  ``(H, D) x (rows, D)^T`` with a row a (token, KV head) pair, and masks
  the rows of other KV heads with the positions a slot may not attend:
  the keys pass through the MXU once either way, and no head is sliced
  out of a tile.  ``_paged_attention``'s mask (``k_pos <= pos``, ``pos -
  k_pos < window``), its scale after the product, float32 scores and
  softmax (online here: running maximum, sum and accumulator), the
  probabilities rounded to the cache's dtype before the weighted sum,
  float32 accumulation; the one difference is where the sum is divided
  (once, at the end).  A masked row's probability is exactly 0, so what
  scratch block 0 and the blocks past a slot's length hold is never
  seen, as long as it is finite.

``kernel_takes`` is the rule by which ``PagedKVPrograms`` chooses this
form over the XLA one; the XLA form stays the reference of the tests.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_kernels
from .pallas_kernels import _NEG_INF, _named_kernel

#: the kernel's scope and ``name=``: a row of its own in a device trace
KERNEL_NAME = "paged_decode_attention"

#: table entries a copy step takes (fewer where the table is narrower):
#: 8 blocks of 16 tokens x 8 KV heads are 1,024 rows, 256 KiB a buffer
BLOCKS_PER_STEP = 8


def kernel_takes(pool_shape, n_heads, dtype):
    """Whether Mosaic is handed pools of this shape (``(..., block_tokens,
    KV, D)``): a bfloat16 cache, heads of whole 128-lane tiles, KV heads
    in eights (a token's heads are then whole ``(8, 128)`` tiles in HBM,
    so the kernel's view of a block as ``block_tokens x KV`` rows is the
    carried pool's own bytes and no copy), a block of whole ``(16,
    128)`` tiles of rows, and more than one query head a KV head (at one
    the product feeds the MXU a single row a head; no deployment here
    has it, and it keeps the XLA form).  What the chip has measured is
    Mistral's 32 heads over 8 KV heads of 128 in blocks of 16."""
    bt, kv, d = pool_shape[-3:]
    return (jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)
            and d % 128 == 0 and kv % 8 == 0 and (bt * kv) % 16 == 0
            and n_heads % kv == 0 and n_heads > kv)


def _floor_div_mod(x, n):
    """``(x // n, x % n)`` of non-negative int32 lanes; shifts where
    ``n`` is a power of two."""
    if n & (n - 1) == 0:
        shift = n.bit_length() - 1
        return x >> shift, x & (n - 1)
    return jax.lax.div(x, jnp.int32(n)), jax.lax.rem(x, jnp.int32(n))


def _paged_kernel(tables_ref, pos_ref, base_ref, q_ref, k_hbm, v_hbm,
                  o_ref, k_buf, v_buf, sems, parity, *, bt, kv, blocks,
                  window):
    slot = pl.program_id(0)
    slots = pl.num_programs(0)
    base = base_ref[0]
    heads, d = q_ref.shape[1:]
    rows = bt * kv                      # a block's (token, KV head) rows
    width = blocks * rows

    def steps_of(s):
        # table entries [first, last] hold what slot ``s`` may attend;
        # steps are aligned groups of ``blocks`` entries (the table's
        # width is a multiple), so a step never reads past the table
        p = pos_ref[s]
        last = p // bt
        first = 0 if window is None \
            else jnp.maximum(p - window + 1, 0) // bt
        return first // blocks, last // blocks + 1

    pos = pos_ref[slot]
    step0, step1 = steps_of(slot)

    def copies(s, step, buf):
        out = []
        for j in range(blocks):
            block = tables_ref[s, step * blocks + j] + base
            dst = pl.ds(j * rows, rows)
            out.append(pltpu.make_async_copy(
                k_hbm.at[block], k_buf.at[buf, dst], sems.at[0, buf]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[block], v_buf.at[buf, dst], sems.at[1, buf]))
        return out

    @pl.when(slot == 0)
    def _():
        parity[0] = 0
        for copy in copies(slot, step0, 0):
            copy.start()

    q = q_ref[0]
    scale = np.float32(1.0 / np.sqrt(d))
    col = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    col_token, col_head = _floor_div_mod(col, kv)
    row_head = jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, (heads, 1), 0),
        jnp.int32(heads // kv))
    own_head = col_head == row_head      # (heads, width)

    def body(step, carry):
        m, l, acc = carry
        buf = parity[0]

        @pl.when(step + 1 < step1)
        def _():
            for copy in copies(slot, step + 1, 1 - buf):
                copy.start()

        # ... or, at a slot's last step, the next slot's first: the
        # grid runs the slots in order, so a slot finds its first
        # blocks on their way and ``parity`` says into which buffer
        @pl.when((step + 1 == step1) & (slot + 1 < slots))
        def _():
            for copy in copies(slot + 1, steps_of(slot + 1)[0], 1 - buf):
                copy.start()

        for copy in copies(slot, step, buf):
            copy.wait()
        parity[0] = 1 - buf
        s = jax.lax.dot_general(
            q, k_buf[buf], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        k_pos = step * (blocks * bt) + col_token
        seen = k_pos <= pos
        if window is not None:
            seen = seen & (pos - k_pos < window)
        # every step holds a key each head may attend (the aligned
        # group of ``first`` or of ``last``, or one between), so the
        # running maximum is a real score from the first step on and
        # exp() of a masked score is exactly 0
        s = jnp.where(own_head & seen, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jax.lax.dot_general(
            p.astype(v_buf.dtype), v_buf[buf], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        step0, step1, body,
        (jnp.full((heads, 1), _NEG_INF, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, d), jnp.float32)))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, tables, pos, base, *,
                           window=None, blocks_per_step=None,
                           interpret=None):
    """Each slot's one query ``q (B, H, D)`` against the keys and values
    its block table names in the flat pools ``(blocks, block_tokens, KV,
    D)``, positions ``0 .. pos[b]`` (inside ``window`` where given);
    ``tables (B, NB)`` int32 holds ids relative to ``base``.  Returns
    ``(B, H, D)`` in ``q``'s dtype.  ``interpret=None`` follows
    ``pallas_kernels.default_interpret()``."""
    if interpret is None:
        interpret = pallas_kernels.default_interpret()
    B, H, D = q.shape
    n_total, bt, kv, _ = k_pool.shape
    NB = tables.shape[1]
    blocks = min(blocks_per_step or BLOCKS_PER_STEP, NB)
    if NB % blocks or H % kv:
        raise ValueError(
            f"table width {NB} must be a multiple of the {blocks} blocks "
            f"a step takes, and {H} heads of the {kv} KV heads")
    rows = bt * kv
    kernel = functools.partial(
        _paged_kernel, bt=bt, kv=kv, blocks=blocks, window=window)
    call = _named_kernel(
        KERNEL_NAME, kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, blocks * rows, D), k_pool.dtype),
                pltpu.VMEM((2, blocks * rows, D), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret)
    # a block as (token, KV head) rows: the same bytes (kernel_takes)
    flat = (n_total, rows, D)
    return call(tables.astype(jnp.int32),
                pos.astype(jnp.int32),
                jnp.reshape(base, (1,)).astype(jnp.int32),
                q, k_pool.reshape(flat), v_pool.reshape(flat))
