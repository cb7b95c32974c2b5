"""Paged KV cache for continuous-batching LM serving
(docs/serving.md "Continuous batching").

The decode hot path must hit :mod:`..ops.compiled`'s shared program
cache on EVERY step — "zero steady-state recompiles" is an acceptance
gate asserted from the cache counters — so every shape here is
bucketed and fixed:

* K/V live in two pools of shape ``(L, n_blocks, block_tokens, KV,
  D)``; a sequence owns an ordered list of block ids (its *block
  table*) and decode reads its cache through that table.  Pools never
  change shape; sequences joining or leaving only changes table
  contents (operands, not shapes).
* Block 0 is reserved **scratch**: padded table entries and
  inactive-slot writes land there.  Its contents are garbage by
  design — every read of it is masked to a -1e30 score, which softmax
  turns into an exactly-0.0 probability, so the garbage is never
  observable in any output.
* The decode tick carries both pools through its layer loop as the
  loop's CARRY and updates them in place: viewed flat as ``(L *
  n_blocks, block_tokens, KV, D)``, layer ``l`` writes its new rows
  with one scatter at ``block + l * n_blocks`` and then attends the
  carry at ``table + l * n_blocks``.  They must NOT
  ride the loop as its ``xs`` / ``ys``: the loop then slices each
  layer's slab out of a pool, writes it into a new stacked output,
  and the donated argument, which cannot alias an output built slab
  by slab, is copied once more — three passes over both pools every
  tick, whatever the batch (38 of an 82 ms tick on a v5e at 16
  layers x 4,096 blocks; PERF.md section 6, PR 43).
* Decode attends the cache IN PLACE where it can.  The attention of
  a tick has two forms, one algorithm, one mask, one rounding
  (:func:`kernel_interpret` is the rule, no knob): where the process
  computes on a TPU and the shapes fill its tiles, a Pallas kernel
  (:mod:`..ops.paged_kernels`) follows each slot's table through the
  carried pools up to the slot's own position, a slot costing what it
  holds; elsewhere (the CPU tests) the XLA form gathers every slot's
  whole table into a dense view, keys and values, every layer, and
  scores all of it, so every slot pays for the widest active table.
  The XLA form is the kernel's reference in the tests.  The kernel
  reads the carry as it is (a block as ``block_tokens x KV`` rows is
  the same bytes): a block laid out otherwise is only to be had by
  STORING the pools so (``pool_shape``, ``make_pools``, ingest, the
  scatters, whatever reads rows back), never by reshaping the carry,
  which copies a pool every tick when bytes change tiles.
* One decode program per block-table width bucket (powers of two),
  always at batch ``max_slots`` with a per-slot active mask; one
  prefill + one ingest program per prompt-length bucket.  Warmup
  compiles the full set; after that the cache-miss counter must not
  move.

Prefill is split from ingest on purpose: prefill computes the
sequence's per-layer K/V (and its greedy first token — TTFT is
measured to this), ingest scatters them into the pools.  Run back to
back they are the monolithic path; the prefill/decode-split path
inserts the quantized wire (:func:`pack_kv_blocks` /
:func:`unpack_kv_blocks`) between the same two programs, so both
deployments share one compiled vocabulary.

What a layer, the embedding and the head COMPUTE is
:mod:`..models.transformer`'s own code: each forward scans the stacked
parameters with ``DecoderBlock``'s two halves (``qkv``, ``finish``)
applied around the one thing that is serving's, the attention inner
over the cache, so continuous-batched greedy decode is
token-identical to :func:`..models.transformer.make_generate_fn` under
every field of the config the layer reads — the parity property the
tests and the serve smoke pin.
"""

import functools
import threading

import numpy as np

import jax
import jax.numpy as jnp

from .. import telemetry
from ..models.transformer import (
    DecoderBlock, RMSNorm, dense_causal_attention, embed_tokens,
    grouped_causal_attention, lm_logits, rope_angles,
)
from ..ops import compiled as compiled_mod
from ..ops import paged_kernels, pallas_kernels
from ..ops import quantize as quantize_mod

__all__ = [
    "KVBlockPool", "PagedKVPrograms", "BlocksExhausted",
    "bucket_for", "pow2_buckets", "pack_kv_blocks", "unpack_kv_blocks",
]


def pow2_buckets(n_max):
    """Powers of two up to and including the first one >= ``n_max``."""
    if n_max < 1:
        raise ValueError(f"n_max must be >= 1, got {n_max}")
    out = []
    b = 1
    while True:
        out.append(b)
        if b >= n_max:
            return tuple(out)
        b *= 2


def bucket_for(n, buckets):
    """Smallest bucket >= ``n`` (buckets ascending)."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} exceeds largest bucket {buckets[-1]}")


class BlocksExhausted(RuntimeError):
    """The pool has no free blocks — admission control's signal to
    queue the sequence rather than grow a shape."""


class KVBlockPool:
    """Host-side block allocator over the device pools.

    Deterministic by construction: ``alloc`` always hands out the
    lowest-numbered free blocks, so the same admission order yields
    the same tables on every same-seed run (the byte-identical drill
    evidence depends on this).  Block 0 is never allocated (scratch).
    ``free`` rejects double-frees and foreign ids loudly — the
    zero-leaked-blocks drain check is only as good as the accounting.
    """

    def __init__(self, n_blocks, block_tokens):
        if n_blocks < 2:
            raise ValueError(
                f"n_blocks must be >= 2 (block 0 is scratch), "
                f"got {n_blocks}")
        if block_tokens < 1:
            raise ValueError(
                f"block_tokens must be >= 1, got {block_tokens}")
        self.n_blocks = int(n_blocks)
        self.block_tokens = int(block_tokens)
        self._free = list(range(1, self.n_blocks))   # ascending
        self._lock = threading.Lock()
        self._publish()

    @property
    def capacity(self):
        return self.n_blocks - 1

    @property
    def available(self):
        with self._lock:
            return len(self._free)

    @property
    def in_use(self):
        return self.capacity - self.available

    def alloc(self, n=1):
        """Lowest ``n`` free block ids, or :class:`BlocksExhausted`."""
        if n < 1:
            raise ValueError(f"alloc count must be >= 1, got {n}")
        with self._lock:
            if n > len(self._free):
                raise BlocksExhausted(
                    f"need {n} KV blocks, {len(self._free)} free "
                    f"(capacity {self.capacity})")
            blocks = self._free[:n]
            del self._free[:n]
        self._publish()
        return blocks

    def free(self, blocks):
        with self._lock:
            ids = [int(b) for b in blocks]
            for i, b in enumerate(ids):
                if b < 1 or b >= self.n_blocks:
                    raise ValueError(f"block {b} not allocatable")
                if b in self._free or b in ids[:i]:
                    raise ValueError(f"double free of KV block {b}")
            self._free = sorted(self._free + ids)
        self._publish()

    def _publish(self):
        telemetry.set_kv_blocks_in_use(self.in_use)


# ---------------------------------------------------------------------------
# pure forwards (jitted once per bucket through the shared program cache)


def _paged_attention(q, k, v, q_pos, window):
    """q (B, 1, H, D) against gathered block views k/v (B, S, KV, D)
    with per-slot query positions (B,): valid keys are k_pos <= q_pos
    (and inside the sliding window).  Scratch-block rows fail the
    position test and contribute exactly 0."""
    B, T, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, T, KV, G, D)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(D)
    k_pos = jnp.arange(S)[None, :]
    mask = k_pos <= q_pos[:, None]
    if window is not None:
        mask = mask & (q_pos[:, None] - k_pos < window)
    scores = jnp.where(mask[:, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return o.reshape(B, T, H, D)


def _logits(params, x, cfg):
    """The model's final norm, then its head (the embedding where the
    two are tied), over the last layer's output ``x``."""
    x = RMSNorm(cfg.dtype, cfg.rms_norm_eps).apply(
        {"params": params["ln_final"]}, x)
    return lm_logits(cfg, x, params[
        "embed" if cfg.tie_word_embeddings else "lm_head"])


def _prefill_fwd(params, tokens, length, *, cfg, angles):
    """tokens (1, P) right-padded; returns the greedy token after
    position ``length - 1`` plus the roped per-layer K/V
    ``(L, P, KV, D)`` (rows >= length are garbage ingest discards).
    Attention is dense and causal over the bucket's own P rows."""
    ang = jnp.asarray(angles[:tokens.shape[1]])
    attend = dense_causal_attention if cfg.kv_heads == cfg.n_heads \
        else grouped_causal_attention
    block = DecoderBlock(cfg)

    def body(x, layer):
        layer = {"params": layer}
        h, q, k, v = block.apply(layer, x, ang, method="qkv")
        o = attend(q, k, v, offset=0, window=cfg.attention_window)
        return block.apply(layer, x, h, o, method="finish"), (k[0], v[0])

    x, (k_all, v_all) = jax.lax.scan(
        body, embed_tokens(cfg, params["embed"], tokens), params["layers"])
    logits = _logits(params, x, cfg)
    last = jax.lax.dynamic_slice_in_dim(logits, length - 1, 1, axis=1)
    tok0 = jnp.argmax(last[:, 0], axis=-1).astype(jnp.int32)
    return tok0[0], k_all, v_all


def _ingest_fwd(k_pool, v_pool, k_all, v_all, blocks, length, *, bt):
    """Scatter a prefill's K/V rows into the pools.  Rows past
    ``length`` (bucket padding) target scratch block 0."""
    P = k_all.shape[1]
    p = jnp.arange(P)
    valid = p < length
    blk = jnp.where(valid, blocks[p // bt], 0)
    off = jnp.where(valid, p % bt, 0)
    k_pool = k_pool.at[:, blk, off].set(k_all.astype(k_pool.dtype))
    v_pool = v_pool.at[:, blk, off].set(v_all.astype(v_pool.dtype))
    return k_pool, v_pool


def kernel_interpret(cfg, pool_shape, interpret=None):
    """Which form a decode tick's attention takes: ``None`` for the XLA
    form (gathered views), else the ``interpret=`` the Pallas kernel
    (:func:`..ops.paged_kernels.paged_decode_attention`) is called
    with.  Left to itself (``interpret=None``) the kernel runs where the
    process computes on a TPU and the shapes fill its tiles
    (:func:`..ops.paged_kernels.kernel_takes`); everywhere else, the
    CPU tests among them, the XLA form.  ``True`` / ``False`` force the
    kernel through the interpreter / through Mosaic whatever the
    backend and shapes (the tests; a compile for a described chip)."""
    if interpret is not None:
        return bool(interpret)
    if pallas_kernels.default_interpret() or not paged_kernels.kernel_takes(
            pool_shape, cfg.n_heads, cfg.dtype):
        return None
    return False


def _decode_fwd(params, k_pool, v_pool, toks, pos, tables, active, *,
                cfg, angles, bt, interpret=None):
    """One decode tick for the whole slot batch: feed each slot's
    current token at its own position, write the new K/V into its
    table's block (inactive slots write scratch), attend the slot's
    blocks, return the greedy next token per slot plus the updated
    pools.

    The pools are the layer loop's CARRY, viewed flat as ``(L *
    n_blocks, block_tokens, KV, D)``: layer ``l`` scatters its rows at
    ``blk + l * n_blocks`` and then attends the carry at ``tables + l *
    n_blocks``, so the donated buffers are updated in place (module
    docstring: never as the scan's ``xs`` / ``ys``) and a slot attends
    its own new row.  The attention has two forms, one algorithm
    (:func:`kernel_interpret` chooses; ``interpret`` is its override):
    the Pallas kernel reads each slot's blocks through its table where
    they lie, up to the slot's own position; the XLA form gathers every
    slot's whole table into a dense view and scores all of it.  The
    kernel takes the carry as it is: any other block layout has to be
    the one the pools are STORED in, because a reshape of the carry
    that moves bytes between tiles copies a pool every tick."""
    B, NB = tables.shape
    KV, D = cfg.kv_heads, cfg.head_dim
    pool_shape = k_pool.shape
    L, n_blocks = pool_shape[:2]
    ang = jnp.asarray(angles)[pos][:, None, :]     # (B, 1, D//2)
    blk = jnp.where(
        active,
        jnp.take_along_axis(tables, (pos // bt)[:, None], axis=1)[:, 0],
        0)
    off = jnp.where(active, pos % bt, 0)
    kernel = kernel_interpret(cfg, pool_shape, interpret)
    block = DecoderBlock(cfg)

    def body(carry, layer):
        x, kp, vp, base = carry
        layer = {"params": layer}
        h, q, k, v = block.apply(layer, x, ang, method="qkv")
        kp = kp.at[blk + base, off].set(k[:, 0].astype(kp.dtype))
        vp = vp.at[blk + base, off].set(v[:, 0].astype(vp.dtype))
        if kernel is None:
            kv = kp[tables + base].reshape(B, NB * bt, KV, D)
            vv = vp[tables + base].reshape(B, NB * bt, KV, D)
            o = _paged_attention(q, kv, vv, pos, cfg.attention_window)
        else:
            o = paged_kernels.paged_decode_attention(
                q[:, 0], kp, vp, tables, pos, base,
                window=cfg.attention_window, interpret=kernel)[:, None]
        x = block.apply(layer, x, h, o, method="finish")
        return (x, kp, vp, base + n_blocks), None

    flat = (L * n_blocks,) + pool_shape[2:]
    (x, k_pool, v_pool, _), _ = jax.lax.scan(
        body,
        (embed_tokens(cfg, params["embed"], toks),     # (B, 1, M)
         k_pool.reshape(flat), v_pool.reshape(flat), jnp.int32(0)),
        params["layers"])
    tok = jnp.argmax(_logits(params, x, cfg)[:, 0], axis=-1).astype(jnp.int32)
    return tok, k_pool.reshape(pool_shape), v_pool.reshape(pool_shape)


# ---------------------------------------------------------------------------


class PagedKVPrograms:
    """The bucketed compiled vocabulary over the pools, every program
    registered in the process-wide shared program cache (keys
    namespaced ``("paged_kv", kind, sig, bucket)``) so steady-state
    recompiles are assertable from
    :func:`..ops.compiled.program_cache_stats`."""

    def __init__(self, cfg, *, max_slots, block_tokens, n_blocks,
                 prompt_buckets=None, donate=None, interpret=None):
        if cfg.layer_types is not None:
            raise ValueError(
                "paged-KV serving holds one kind of layer in one cache: "
                "a model with layer_types (layers of several kinds, "
                "state-space layers, a looped model) has no KV-cache path")
        if cfg.num_experts:
            raise ValueError(
                "paged-KV decode supports dense-MLP models only "
                "(num_experts must be 0)")
        if cfg.head_dim % 2:
            raise ValueError("head_dim must be even (rope pairing)")
        self.cfg = cfg
        self.max_slots = int(max_slots)
        self.block_tokens = int(block_tokens)
        self.n_blocks = int(n_blocks)
        if self.max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        nb_max = -(-cfg.max_seq_len // self.block_tokens)
        self.table_buckets = pow2_buckets(nb_max)
        if prompt_buckets is None:
            prompt_buckets = tuple(
                b for b in pow2_buckets(cfg.max_seq_len)
                if b >= min(8, cfg.max_seq_len))
        self.prompt_buckets = tuple(sorted(set(
            int(b) for b in prompt_buckets)))
        if self.prompt_buckets[-1] > cfg.max_seq_len:
            raise ValueError(
                f"prompt bucket {self.prompt_buckets[-1]} exceeds "
                f"max_seq_len {cfg.max_seq_len}")
        self._angles = rope_angles(cfg.head_dim, cfg.max_seq_len,
                                   cfg.rope_theta)
        if donate is None:
            donate = jax.default_backend() != "cpu"
        self._donate = bool(donate)
        # the whole config: every field of it the layer reads is traced
        # into the programs
        self._sig = (cfg, self.max_slots, self.block_tokens, self.n_blocks)
        # whether the decode programs' attention is the kernel's: static
        # a process, so decode() counts its ticks on the host
        self._interpret = interpret
        self.reads_in_place = kernel_interpret(
            cfg, self.pool_shape, interpret) is not None

    # -- pools ---------------------------------------------------------------

    @property
    def pool_shape(self):
        cfg = self.cfg
        return (cfg.n_layers, self.n_blocks, self.block_tokens,
                cfg.kv_heads, cfg.head_dim)

    def make_pools(self):
        z = jnp.zeros(self.pool_shape, self.cfg.dtype)
        return z, jnp.zeros_like(z)

    def blocks_for(self, n_tokens):
        """Blocks a sequence of ``n_tokens`` occupies."""
        return -(-int(n_tokens) // self.block_tokens)

    def table_bucket(self, n_blocks):
        return bucket_for(max(1, n_blocks), self.table_buckets)

    def prompt_bucket(self, n_tokens):
        return bucket_for(n_tokens, self.prompt_buckets)

    # -- compiled programs ---------------------------------------------------

    def _prefill_program(self, P):
        key = ("paged_kv", "prefill", self._sig, P)
        cfg, ang = self.cfg, self._angles

        def build():
            return jax.jit(functools.partial(
                _prefill_fwd, cfg=cfg, angles=ang))

        return compiled_mod.shared_program(key, build)

    def _ingest_program(self, P):
        key = ("paged_kv", "ingest", self._sig, P)
        bt = self.block_tokens
        donate = (0, 1) if self._donate else ()

        def build():
            return jax.jit(functools.partial(_ingest_fwd, bt=bt),
                           donate_argnums=donate)

        return compiled_mod.shared_program(key, build)

    def _decode_program(self, NB):
        key = ("paged_kv", "decode", self._sig, NB, self._interpret)
        cfg, ang, bt = self.cfg, self._angles, self.block_tokens
        donate = (1, 2) if self._donate else ()
        # the override rides only where one was given: what stands in
        # for _decode_fwd (chipbench/tools/serve_fault.py) knows the
        # three keywords it always had
        forced = {} if self._interpret is None \
            else {"interpret": self._interpret}

        def build():
            return jax.jit(functools.partial(
                _decode_fwd, cfg=cfg, angles=ang, bt=bt, **forced),
                donate_argnums=donate)

        return compiled_mod.shared_program(key, build)

    # -- public entry points -------------------------------------------------

    def prefill(self, params, token_ids):
        """Run the prompt through its length bucket's program;
        returns ``(first_token, k_all, v_all)`` with k/v shaped
        ``(L, P_bucket, KV, D)`` (rows >= len(token_ids) garbage)."""
        ids = np.asarray(token_ids, np.int32).reshape(-1)
        if ids.size < 1:
            raise ValueError("empty prompt")
        P = self.prompt_bucket(ids.size)
        padded = np.zeros((1, P), np.int32)
        padded[0, :ids.size] = ids
        tok0, k_all, v_all = self._prefill_program(P)(
            params, jnp.asarray(padded),
            jnp.asarray(ids.size, jnp.int32))
        return int(tok0), k_all, v_all

    def ingest(self, k_pool, v_pool, k_all, v_all, blocks, length):
        """Scatter ``k_all``/``v_all[:, :length]`` into the pools at
        ``blocks`` (one id per occupied block, position order)."""
        P = int(k_all.shape[1])
        need = self.blocks_for(length)
        if len(blocks) != need:
            raise ValueError(
                f"{length} tokens occupy {need} blocks, got "
                f"{len(blocks)}")
        padded = np.zeros(self.blocks_for(P), np.int32)
        padded[:need] = np.asarray(blocks, np.int32)
        return self._ingest_program(P)(
            k_pool, v_pool, k_all, v_all, jnp.asarray(padded),
            jnp.asarray(int(length), jnp.int32))

    def decode(self, params, k_pool, v_pool, toks, positions, tables,
               active):
        """One tick over the full slot batch.  ``tables`` must already
        be padded to a table bucket width (scratch id 0); ``toks`` /
        ``positions`` / ``active`` are dense over ``max_slots``.
        Returns ``(next_tokens (B,) np.int32, k_pool, v_pool)``."""
        tables = np.asarray(tables, np.int32)
        B, NB = tables.shape
        if B != self.max_slots:
            raise ValueError(
                f"decode batch is always max_slots={self.max_slots}, "
                f"got {B}")
        if NB not in self.table_buckets:
            raise ValueError(
                f"table width {NB} not a bucket {self.table_buckets}")
        tok, k_pool, v_pool = self._decode_program(NB)(
            params, k_pool, v_pool,
            jnp.asarray(np.asarray(toks, np.int32))[:, None],
            jnp.asarray(np.asarray(positions, np.int32)),
            jnp.asarray(tables),
            jnp.asarray(np.asarray(active, bool)))
        telemetry.count_serve_decode_tick(self.reads_in_place)
        return np.asarray(tok), k_pool, v_pool

    def warmup(self, params):
        """Compile the whole bucketed vocabulary up front (throwaway
        pools) so serving's steady state never misses the program
        cache.  Returns the number of programs exercised."""
        k_pool, v_pool = self.make_pools()
        n = 0
        bt = self.block_tokens
        for P in self.prompt_buckets:
            ids = np.zeros(min(P, bt), np.int32)
            _, k_all, v_all = self.prefill(params, ids)
            k_pool, v_pool = self.ingest(
                k_pool, v_pool, k_all, v_all,
                list(range(1, 1 + self.blocks_for(ids.size))),
                ids.size)
            n += 2
        toks = np.zeros(self.max_slots, np.int32)
        pos = np.zeros(self.max_slots, np.int32)
        active = np.zeros(self.max_slots, bool)
        active[0] = True
        pos[0] = min(bt, self.cfg.max_seq_len) - 1
        for NB in self.table_buckets:
            tables = np.zeros((self.max_slots, NB), np.int32)
            tables[0, 0] = 1
            _, k_pool, v_pool = self.decode(
                params, k_pool, v_pool, toks, pos, tables, active)
            n += 1
        jax.block_until_ready((k_pool, v_pool))
        return n


# ---------------------------------------------------------------------------
# the KV wire codec (prefill -> decode hop on the split path)


_KV_WIRE_KINDS = ("f32", "int8", "int4")


def pack_kv_blocks(k_all, v_all, length, wire="int8"):
    """Encode a prefill's K/V rows ``[:length]`` for the
    prefill->decode hop — the same blockwise codec the training wire
    uses (:mod:`..ops.quantize`), so the split path inherits its
    compression and its determinism.  ``wire`` in ``{"f32", "int8",
    "int4"}``; f32 ships full width (lossless, parity-exact)."""
    if wire not in _KV_WIRE_KINDS:
        raise ValueError(
            f"kv wire must be one of {_KV_WIRE_KINDS}, got {wire!r}")
    k = np.asarray(k_all)[:, :length]
    v = np.asarray(v_all)[:, :length]
    msg = {"wire": wire, "shape": k.shape, "dtype": str(k.dtype),
           "length": int(length)}
    for name, arr in (("k", k), ("v", v)):
        if wire == "f32":
            msg[name] = np.ascontiguousarray(arr, np.float32)
        elif wire == "int8":
            q, s, n = quantize_mod.np_quantize_blockwise(arr)
            msg[name] = (q, s, n)
        else:
            q, s, n = quantize_mod.np_quantize_blockwise_int4(arr)
            msg[name] = (q, s, n)
    return msg


def unpack_kv_blocks(msg):
    """Inverse of :func:`pack_kv_blocks`; returns ``(k, v, length)``
    as numpy arrays shaped ``(L, length, KV, D)`` in the pool dtype's
    widening float32 (ingest casts to the pool dtype)."""
    wire = msg["wire"]
    shape = tuple(msg["shape"])
    out = []
    for name in ("k", "v"):
        if wire == "f32":
            out.append(np.asarray(msg[name], np.float32))
        elif wire == "int8":
            q, s, n = msg[name]
            out.append(quantize_mod.np_dequantize_blockwise(
                q, s, n).reshape(shape))
        elif wire == "int4":
            q, s, n = msg[name]
            out.append(quantize_mod.np_dequantize_blockwise_int4(
                q, s, n).reshape(shape))
        else:
            raise ValueError(f"unknown kv wire {wire!r}")
    return out[0], out[1], int(msg["length"])
