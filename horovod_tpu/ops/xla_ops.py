"""Compiled XLA collective executors — the TPU-native data plane.

This replaces the reference's entire ops layer
(``horovod/common/ops/{nccl,mpi,gloo,ccl}_operations.cc``): instead of
hand-written NCCL/MPI calls on fusion buffers, each (possibly fused)
collective is a **cached, jit-compiled XLA program over a
jax.sharding.Mesh** whose collectives (`lax.psum`, `lax.all_gather`,
`lax.all_to_all`, `lax.psum_scatter`) lower onto ICI.  The program
cache plays the role the response cache plays in the reference
(response_cache.h:45-101): steady-state iterations hit an already
compiled program keyed by (op, shape, dtype, reduce-op, ...).

Execution modes:

* **shard mode** (one device per rank): the global array is sharded
  over mesh axis ``'hvd'`` and the collective is a ``shard_map``
  program — the idiomatic TPU path.  Works single-process or
  **multi-process** (after ``jax.distributed.initialize``): each
  process supplies shards for the ranks it hosts and the same program
  runs SPMD everywhere, collectives riding ICI/DCN.
* **stacked mode** (single-process fallback, any rank count): the
  per-rank buffers are stacked on one device and reduced with plain
  jnp ops in one compiled program.  Used when ranks oversubscribe
  devices (unit tests, or many rank-threads on one chip).

All host→device staging happens once per fused bucket (one
``device_put`` per locally-hosted rank), matching the reference's
one-memcpy-per-fusion-buffer design (collective_operations.h:38-343).

Row convention: every method takes ``rows`` = one flat host buffer per
**locally hosted** rank (ordered by global rank), and returns outputs
for those same local ranks; metadata spanning all ranks (allgather
dim0s, alltoall splits) is passed explicitly, negotiated by the
controller exactly as the reference exchanges shapes during
negotiation (controller.cc:901-1080).
"""

import os
import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common.shard_compat import shard_map  # noqa: F401  (re-export)
from ..core.message import ReduceOp
from . import adasum as adasum_ops


def _scale_np_dtype(dtype):
    """Host dtype for scale factors, following the reference's math:
    the tensor's own precision for f64 tensors (its CPU path scales in
    the tensor dtype and the tests compare exactly at small sizes),
    FP64 for integer tensors (scale-then-truncate), f32 for everything
    else.  64-bit math needs x64; otherwise f32 is the best
    available."""
    x64 = jax.config.jax_enable_x64
    if str(dtype) != "bfloat16" and np.dtype(dtype) == np.float64:
        return np.float64 if x64 else np.float32
    if _is_float(dtype):
        return np.float32
    return np.float64 if x64 else np.float32


def _scale_jnp_dtype(dtype):
    return jnp.dtype(_scale_np_dtype(dtype))


def _is_float(dtype) -> bool:
    return jnp.issubdtype(np.dtype(dtype), jnp.floating) or str(dtype) == "bfloat16"


class MeshExecutor:
    """Executes collectives for one process set.

    The reference binds one NCCL communicator per (stream, device-set)
    (nccl_operations.h:44-56); here the analogue is one Mesh + program
    cache per process set.

    ``devices``: one device per member rank of the set (global order).
    ``local_positions``: indices (into the set) of the ranks this
    process hosts; ``None`` = all (single-process).
    """

    def __init__(self, devices, num_ranks, local_positions=None):
        self.devices = list(devices)
        self.num_ranks = num_ranks
        if local_positions is None:
            local_positions = list(range(num_ranks))
        self.local_positions = list(local_positions)
        self.multihost = len(self.local_positions) < num_ranks
        one_dev_per_rank = (num_ranks == len(self.devices)
                            and len(set(self.devices)) == len(self.devices))
        self.shard_mode = one_dev_per_rank and (num_ranks > 1
                                                or self.multihost)
        if self.multihost and not self.shard_mode:
            raise ValueError(
                "multi-process execution requires one device per rank")
        if self.shard_mode:
            self.mesh = Mesh(np.array(self.devices), ("hvd",))
            self._row_sharding = NamedSharding(self.mesh, P("hvd"))
            self._rep_sharding = NamedSharding(self.mesh, P())
        else:
            self.mesh = None
        # 2-D reshapes of the SAME member devices, keyed by inner-axis
        # size (hierarchical / torus decompositions, mesh2d)
        self._meshes_2d = {}
        self._cache = {}
        self._cache_lock = threading.Lock()
        # Donate the staged input so the collective reuses its HBM
        # (one fused-bucket allocation saved per call).  Only on TPU:
        # a CPU device_put of host memory can be zero-copy and thus
        # not donatable — jax would warn on every call.
        self._donate = (0,) if self.devices and \
            self.devices[0].platform == "tpu" else ()

    # -- program cache ------------------------------------------------------

    def _cached(self, key, builder):
        with self._cache_lock:
            fn = self._cache.get(key)
            if fn is None:
                fn = builder()
                self._cache[key] = fn
            return fn

    def cache_size(self):
        return len(self._cache)

    # -- staging ------------------------------------------------------------

    def _stage_rows(self, rows):
        """rows: one host ndarray per local rank (identical shapes).
        Returns a (R, *shape) jax.Array sharded one-row-per-device in
        shard mode (this process supplying its local shards), or
        stacked on device 0 otherwise."""
        shape = (self.num_ranks,) + tuple(rows[0].shape)
        if self.shard_mode:
            shards = [
                jax.device_put(row[None], self.devices[pos])
                for row, pos in zip(rows, self.local_positions)
            ]
            return jax.make_array_from_single_device_arrays(
                shape, self._row_sharding, shards)
        stacked = np.stack([np.asarray(r) for r in rows])
        return jax.device_put(stacked, self.devices[0])

    def _rows_out(self, arr, dtype=None):
        """Per-rank (sharded) outputs → list of host ndarrays for the
        local ranks, ordered like ``local_positions``.  Results are
        writable copies — users mutate collective outputs in place
        (w -= lr * grad), so read-only device views must not escape.
        ``dtype``: the caller's dtype — without x64 jax narrows 64-bit
        inputs (its platform convention, f32 precision), and the
        result must still round-trip in the submitted dtype."""
        if self.shard_mode:
            by_pos = {}
            for shard in arr.addressable_shards:
                r = shard.index[0].start if isinstance(shard.index[0], slice) \
                    else shard.index[0]
                by_pos[r] = np.array(shard.data)[0]
            rows = [by_pos[pos] for pos in self.local_positions]
        else:
            host = np.asarray(arr)
            rows = [host[pos].copy() for pos in self.local_positions]
        if dtype is not None and rows and rows[0].dtype != dtype:
            rows = [r.astype(dtype) for r in rows]
        return rows

    def _replicated_out(self, arr, dtype=None):
        """Fetch a replicated result once, as a writable host copy;
        callers hand further copies to the remaining local ranks.
        ``dtype`` restores the caller's dtype (see _rows_out)."""
        if self.shard_mode:
            host = np.array(arr.addressable_shards[0].data)
        else:
            host = np.array(arr)
        if dtype is not None and host.dtype != dtype:
            host = host.astype(dtype)
        return host

    def _fanout(self, host):
        """Replicate one host result to every local rank (first is the
        original, the rest copies)."""
        n = len(self.local_positions)
        return [host] + [host.copy() for _ in range(n - 1)]

    # -- allreduce ----------------------------------------------------------

    def allreduce(self, rows, op: ReduceOp, prescale=1.0, postscale=1.0):
        """rows: per-local-rank flat buffers of identical shape (n,).
        Returns list of per-local-rank result buffers (n,)."""
        n = int(rows[0].size)
        dtype = rows[0].dtype
        if n == 0:
            return [np.asarray(r) for r in rows]
        R = self.num_ranks
        is_float = _is_float(dtype)
        if is_float and op == ReduceOp.AVERAGE:
            postscale = postscale / R
            op = ReduceOp.SUM
        # integer tensors support average and pre/post scaling with the
        # reference's semantics (scale in FP64, truncate back —
        # test_torch.py:434-487); average divides rather than
        # multiplying by 1/R so exact multiples stay exact
        scaled = is_float or op == ReduceOp.AVERAGE or \
            prescale != 1.0 or postscale != 1.0
        key = ("allreduce", n, str(dtype), int(op), scaled, self.shard_mode)
        fn = self._cached(key, lambda: self._build_allreduce(n, dtype, op, scaled))
        x = self._stage_rows(rows)
        if scaled:
            sdt = _scale_np_dtype(dtype)
            out = fn(x, sdt(prescale), sdt(postscale))
        else:
            out = fn(x)
        return self._fanout(self._replicated_out(out, dtype))

    def _build_allreduce(self, n, dtype, op, scaled):
        R = self.num_ranks
        sf = _scale_jnp_dtype(dtype)
        avg_int = op == ReduceOp.AVERAGE       # int-average: divide
        if avg_int:
            op = ReduceOp.SUM

        def post_step(y, post):
            if avg_int:
                # divide, don't multiply by 1/R: exact multiples must
                # stay exact under the truncating int cast
                return ((y.astype(sf) / R) * post).astype(dtype)
            return (y.astype(sf) * post).astype(dtype)

        def reduce_block(xb, pre, post):
            # xb: (1, n) in shard mode (per-device row)
            if scaled:
                xb = (xb.astype(sf) * pre).astype(dtype)
            if op == ReduceOp.SUM:
                y = lax.psum(xb, "hvd")
            elif op == ReduceOp.MIN:
                y = lax.pmin(xb, "hvd")
            elif op == ReduceOp.MAX:
                y = lax.pmax(xb, "hvd")
            elif op == ReduceOp.PRODUCT:
                g = lax.all_gather(xb, "hvd", axis=0, tiled=True)
                y = jnp.prod(g, axis=0, keepdims=True, dtype=g.dtype)
            elif op == ReduceOp.ADASUM:
                g = lax.all_gather(xb, "hvd", axis=0, tiled=True)
                y = adasum_ops.adasum_reduce(g)[None]
            else:
                raise ValueError(f"unsupported reduce op {op}")
            if scaled:
                y = post_step(y, post).astype(dtype)
            return y[0]

        def reduce_stacked(x, pre, post):
            # x: (R, n) on one device
            if scaled:
                x = (x.astype(sf) * pre).astype(dtype)
            if op == ReduceOp.SUM:
                # dtype pinned: jnp.sum follows numpy's
                # promote-small-ints-to-default-int rule, which
                # would hand int32 callers int64 results
                y = jnp.sum(x, axis=0, dtype=x.dtype)
            elif op == ReduceOp.MIN:
                y = jnp.min(x, axis=0)
            elif op == ReduceOp.MAX:
                y = jnp.max(x, axis=0)
            elif op == ReduceOp.PRODUCT:
                y = jnp.prod(x, axis=0, dtype=x.dtype)
            elif op == ReduceOp.ADASUM:
                y = adasum_ops.adasum_reduce(x)
            else:
                raise ValueError(f"unsupported reduce op {op}")
            if scaled:
                y = post_step(y, post).astype(dtype)
            return y

        if self.shard_mode:
            mapped = shard_map(
                reduce_block, mesh=self.mesh,
                in_specs=(P("hvd"), P(), P()), out_specs=P(),
                check_vma=False)
            fn = jax.jit(mapped, donate_argnums=self._donate)
        else:
            fn = jax.jit(reduce_stacked, donate_argnums=self._donate)
        if scaled:
            return fn
        return lambda x: fn(x, np.float32(1.0), np.float32(1.0))

    # -- 2-D decomposed allreduce (hierarchical / torus) --------------------
    #
    # The reference's NCCLHierarchicalAllreduce / torus allreduce
    # (nccl_operations.cc:606-830, arXiv:1909.09756) as ONE compiled
    # program over a (outer, inner) reshape of the member devices:
    # reducescatter along the inner (fast / ICI) axis, allreduce of
    # the shards along the outer (slow / DCN) axis, allgather back —
    # only 1/inner of the logical bytes cross the outer hop, and with
    # wire='int8' that hop additionally ships shared-scale quantized
    # integer partials (quantize.quantized_psum_xla).

    def mesh2d(self, inner, axes=("hvd_y", "hvd_x")):
        """Cached (outer-axis, inner-axis) mesh over the same member
        devices, reshaped (num_ranks // inner, inner) row-major —
        inner-axis neighbors stay adjacent in device order, which is
        the ICI-adjacent dimension on a TPU slice (and the intra-host
        ranks for launcher jobs, whose device table is grouped by
        process).  ``axes`` lets callers name the grid (the compiled
        path's TopologyHint, e.g. ("dp", "tp"))."""
        axes = tuple(axes)
        mesh = self._meshes_2d.get((inner, axes))
        if mesh is None:
            if not self.shard_mode:
                raise ValueError(
                    "2-D decompositions need shard mode (one device "
                    "per rank)")
            if inner <= 1 or self.num_ranks % inner:
                raise ValueError(
                    f"inner axis {inner} does not factor world size "
                    f"{self.num_ranks}")
            arr = np.array(self.devices).reshape(
                self.num_ranks // inner, inner)
            mesh = Mesh(arr, axes)
            self._meshes_2d[(inner, axes)] = mesh
        return mesh

    def _stage_rows_2d(self, rows, inner, axes=("hvd_y", "hvd_x")):
        """Like :meth:`_stage_rows` on the (outer, inner) grid: flat
        position p = y * inner + x, matching mesh2d's row-major
        device reshape."""
        mesh = self.mesh2d(inner, axes)
        shape = (self.num_ranks // inner, inner) + tuple(rows[0].shape)
        sharding = NamedSharding(mesh, P(*mesh.axis_names))
        shards = [
            jax.device_put(row[None, None], self.devices[pos])
            for row, pos in zip(rows, self.local_positions)
        ]
        return jax.make_array_from_single_device_arrays(
            shape, sharding, shards)

    def allreduce_2d(self, rows, op: ReduceOp, prescale=1.0,
                     postscale=1.0, inner=1, inner_wire=None,
                     outer_wire=None, wire=None):
        """Two-stage decomposed allreduce with a PER-HOP wire pair.
        ``rows``: per-local-rank flat float buffers (n,); ``inner`` is
        the fast-axis size (host-local ranks for hierarchical, the
        near-square factor for torus).  ``inner_wire`` is the ICI-hop
        format (None = full width, 'fp16'/'bf16' cast the
        psum_scatter and all_gather operands INSIDE the one program);
        ``outer_wire`` is the DCN-hop format (additionally 'int8' /
        'int4': shared-scale quantized integer partials, encode fused
        into the cross psum and decode fused before the gather-back —
        ops/quantize.quantized_psum_xla).  ``wire`` is the legacy
        single-format spelling, treated as the outer wire.  Returns
        per-local-rank result buffers (n,)."""
        if wire is not None and outer_wire is None:
            outer_wire = wire
        n = int(rows[0].size)
        dtype = rows[0].dtype
        if n == 0:
            return [np.asarray(r) for r in rows]
        R = self.num_ranks
        if op == ReduceOp.AVERAGE:
            postscale = postscale / R
            op = ReduceOp.SUM
        if op != ReduceOp.SUM:
            raise ValueError(
                f"2-D decompositions support Sum/Average, got {op}")
        npad = -(-n // inner) * inner
        if npad != n:
            padded = []
            for r in rows:
                buf = np.zeros(npad, dtype=r.dtype)
                buf[:n] = r
                padded.append(buf)
            rows = padded
        key = ("allreduce2d", npad, str(dtype), inner, inner_wire,
               outer_wire)
        fn = self._cached(key, lambda: self._build_allreduce_2d(
            npad, dtype, inner, inner_wire, outer_wire))
        x = self._stage_rows_2d(rows, inner)
        sdt = _scale_np_dtype(dtype)
        out = fn(x, sdt(prescale), sdt(postscale))
        host = self._replicated_out(out, dtype)
        if npad != n:
            host = host[:n]
        return self._fanout(host)

    def _build_allreduce_2d(self, npad, dtype, inner, inner_wire,
                            outer_wire):
        from .quantize import quantized_psum_xla
        outer = self.num_ranks // inner
        sf = _scale_jnp_dtype(dtype)
        mesh = self.mesh2d(inner)
        iw = {"fp16": jnp.float16, "bf16": jnp.bfloat16} \
            .get(inner_wire)

        def body(xb, pre, post):
            # xb: (1, 1, npad) — this device's row on the (y, x) grid
            xb = (xb.astype(sf) * pre).astype(dtype)
            # stage 1 (inner / ICI): reducescatter to 1/inner shards —
            # the inner-wire cast is fused HERE, so only the hop
            # operand narrows (the tensor itself stays full width on
            # the host, unlike the old caller-side row cast which also
            # narrowed the cross hop)
            if iw is not None:
                xb = xb.astype(jnp.float32).astype(iw)
            y = lax.psum_scatter(xb, "hvd_x", scatter_dimension=2,
                                 tiled=True)        # (1, 1, npad/inner)
            # stage 2 (outer / DCN): allreduce of the shard only, over
            # the outer wire — quantized encode/decode fused in-line
            if outer_wire in ("int8", "int4"):
                bits = 8 if outer_wire == "int8" else 4
                y = quantized_psum_xla(y.astype(jnp.float32), "hvd_y",
                                       outer, bits=bits)
            elif outer_wire in ("fp16", "bf16"):
                ow = jnp.float16 if outer_wire == "fp16" \
                    else jnp.bfloat16
                y = lax.psum(y.astype(jnp.float32).astype(ow), "hvd_y")
            else:
                # full-width outer: re-widen a 16-bit inner shard so
                # the DCN psum really accumulates at the tensor dtype
                # (the inner cast narrows ONLY the ICI hop)
                if iw is not None:
                    y = y.astype(dtype)
                y = lax.psum(y, "hvd_y")
            y = (y.astype(sf) * post).astype(dtype)
            # stage 3 (inner / ICI): allgather the reduced shards back,
            # again over the inner wire
            if iw is not None:
                y = y.astype(jnp.float32).astype(iw)
            y = lax.all_gather(y, "hvd_x", axis=2, tiled=True)
            return y.reshape(npad).astype(dtype)

        mapped = shard_map(
            body, mesh=mesh,
            in_specs=(P("hvd_y", "hvd_x"), P(), P()), out_specs=P(),
            check_vma=False)
        return jax.jit(mapped, donate_argnums=self._donate)

    # -- quantized allreduce / reducescatter (int8 / int4 wire) -------------
    #
    # The wire payload is the block-scaled encoding (ops/quantize.py):
    # per 256-element block, integer codes + one bf16 scale — ~3.97x
    # fewer wire bytes than f32 for int8, ~7.9x for the nibble-packed
    # int4 format.  Each rank encodes with its OWN scales; the program
    # moves only the quantized representation (all_gather of codes +
    # scales), decodes per rank and reduces in f32 — so the reduction
    # is exactly the sum of the values each rank's error-feedback
    # residual was computed against.  (The compiled in-graph path uses
    # the shared-scale psum-of-integer-partials variant instead —
    # ops/compiled.py.)

    def allreduce_quantized(self, q_rows, scale_rows, op: ReduceOp,
                            prescale=1.0, postscale=1.0, nbits=8,
                            n_elems=None):
        """q_rows: per-local-rank int8 codes (npad,) — or packed uint8
        nibbles (npad/2,) for ``nbits=4``; scale_rows: per-local-rank
        f32 scales (nb,).  ``n_elems``: the padded element count
        (defaults to the int8 layout's code count).  Returns
        per-local-rank f32 result buffers (n_elems,) — callers slice
        to the true length."""
        npad = int(n_elems) if n_elems is not None \
            else int(q_rows[0].size)
        nb = int(scale_rows[0].size)
        R = self.num_ranks
        post = float(prescale) * float(postscale)
        if op == ReduceOp.AVERAGE:
            post /= R
        elif op != ReduceOp.SUM:
            raise ValueError(
                f"quantized wire supports Sum/Average allreduce, "
                f"got {op}")
        key = ("allreduce_q", npad, nb, nbits, self.shard_mode)
        fn = self._cached(key, lambda: self._build_allreduce_quantized(
            npad, nb, nbits))
        q = self._stage_rows(q_rows)
        s = self._stage_rows(scale_rows)
        out = fn(q, s, np.float32(post))
        return self._fanout(self._replicated_out(out, np.float32))

    @staticmethod
    def _dequant_fn(nbits, npad):
        """Shared decode dispatch: (R, codes) x (R, nb) -> (R, npad)
        f32 via the wire codec, so device and host decode
        bit-identically for both widths."""
        from .quantize import (dequantize_blockwise_int4_xla,
                               dequantize_blockwise_xla)

        def dequant(qg, sg):
            if nbits == 4:
                return dequantize_blockwise_int4_xla(
                    qg, sg.astype(jnp.float32), npad)
            return dequantize_blockwise_xla(
                qg, sg.astype(jnp.float32), npad)
        return dequant

    def _build_allreduce_quantized(self, npad, nb, nbits):
        dequant = self._dequant_fn(nbits, npad)

        def body(qb, sb, post):
            qg = lax.all_gather(qb, "hvd", axis=0, tiled=True)
            sg = lax.all_gather(sb, "hvd", axis=0, tiled=True)
            return jnp.sum(dequant(qg, sg), axis=0) * post

        def stacked(q, s, post):
            return jnp.sum(dequant(q, s), axis=0) * post

        if self.shard_mode:
            mapped = shard_map(
                body, mesh=self.mesh,
                in_specs=(P("hvd"), P("hvd"), P()), out_specs=P(),
                check_vma=False)
            return jax.jit(mapped)
        return jax.jit(stacked)

    def reducescatter_quantized(self, q_rows, scale_rows, d0,
                                rest_shape, op: ReduceOp,
                                prescale=1.0, postscale=1.0, nbits=8,
                                n_elems=None):
        """Quantized variant of :meth:`reducescatter`: ``q_rows`` /
        ``scale_rows`` encode the padded (R * max_chunk * rest,)
        layout (packed nibbles for ``nbits=4``).  Returns
        per-local-rank f32 (chunk_j, *rest)."""
        npad = int(n_elems) if n_elems is not None \
            else int(q_rows[0].size)
        nb = int(scale_rows[0].size)
        R = self.num_ranks
        chunks = self.chunk_sizes(d0, R)
        max_chunk = max(chunks) if chunks else 0
        rest = int(np.prod(rest_shape, dtype=np.int64)) if rest_shape else 1
        m = max_chunk * rest
        post = float(prescale) * float(postscale)
        if op == ReduceOp.AVERAGE:
            post /= R
        elif op != ReduceOp.SUM:
            raise ValueError(
                f"quantized wire supports Sum/Average reducescatter, "
                f"got {op}")
        key = ("reducescatter_q", npad, nb, m, nbits, self.shard_mode)
        fn = self._cached(key, lambda: self._build_reducescatter_quantized(
            npad, nb, m, nbits))
        q = self._stage_rows(q_rows)
        s = self._stage_rows(scale_rows)
        out = fn(q, s, np.float32(post))
        per_local = self._rows_out(out, np.float32)
        return [
            row[: chunks[pos] * rest].reshape(
                (chunks[pos],) + tuple(rest_shape))
            for row, pos in zip(per_local, self.local_positions)
        ]

    def _build_reducescatter_quantized(self, npad, nb, m, nbits):
        R = self.num_ranks
        dequant = self._dequant_fn(nbits, npad)

        def body(qb, sb, post):
            qg = lax.all_gather(qb, "hvd", axis=0, tiled=True)
            sg = lax.all_gather(sb, "hvd", axis=0, tiled=True)
            x = dequant(qg, sg)
            idx = lax.axis_index("hvd")
            # both indices must share a dtype (x64 mode canonicalizes
            # the literal 0 to int64 while axis_index is int32)
            tile = lax.dynamic_slice(
                x, (jnp.zeros((), jnp.int32),
                    (idx * m).astype(jnp.int32)), (R, m))
            return jnp.sum(tile, axis=0, keepdims=True) * post

        def stacked(q, s, post):
            x = dequant(q, s)[:, : R * m].reshape(R, R, m)
            return jnp.sum(x, axis=0) * post

        if self.shard_mode:
            mapped = shard_map(
                body, mesh=self.mesh,
                in_specs=(P("hvd"), P("hvd"), P()), out_specs=P("hvd"),
                check_vma=False)
            return jax.jit(mapped)
        return jax.jit(stacked)

    # -- allgather ----------------------------------------------------------

    def allgather(self, rows, dim0_sizes, rest_shape):
        """Concatenate per-rank tensors along dim 0.  ``rows`` are the
        per-local-rank buffers already padded+flattened to
        (max_d0 * rest,) by the caller; ``dim0_sizes`` are ALL ranks'
        true first-dim sizes (negotiated cross-rank, like the
        reference's allgather shape exchange)."""
        dtype = rows[0].dtype
        rest = int(np.prod(rest_shape, dtype=np.int64)) if rest_shape else 1
        max_d = max(dim0_sizes) if dim0_sizes else 0
        if max_d == 0 or rest == 0:
            empty = np.zeros((0,) + tuple(rest_shape), dtype=dtype)
            return [empty.copy() for _ in self.local_positions]
        key = ("allgather", tuple(dim0_sizes), tuple(rest_shape), str(dtype),
               self.shard_mode)
        fn = self._cached(key, lambda: self._build_allgather(
            tuple(dim0_sizes), tuple(rest_shape), dtype))
        x = self._stage_rows(rows)
        out = fn(x)
        host = self._replicated_out(out, dtype)
        result_shape = (sum(dim0_sizes),) + tuple(rest_shape)
        return self._fanout(host.reshape(result_shape))

    def _build_allgather(self, dim0_sizes, rest_shape, dtype):
        R = self.num_ranks
        rest = int(np.prod(rest_shape, dtype=np.int64)) if rest_shape else 1

        def unpad_concat(g):
            # g: (R, max_d * rest) — slice each rank's true rows, concat.
            parts = [g[r, : dim0_sizes[r] * rest] for r in range(R)]
            return jnp.concatenate(parts)

        def gather_block(xb):
            g = lax.all_gather(xb, "hvd", axis=0, tiled=True)
            return unpad_concat(g)

        if self.shard_mode:
            mapped = shard_map(
                gather_block, mesh=self.mesh,
                in_specs=(P("hvd"),), out_specs=P(),
                check_vma=False)
            return jax.jit(mapped, donate_argnums=self._donate)
        return jax.jit(unpad_concat, donate_argnums=self._donate)

    # -- broadcast ----------------------------------------------------------

    def broadcast(self, rows, root_pos):
        n = int(rows[0].size)
        dtype = rows[0].dtype
        if n == 0:
            return [np.asarray(r) for r in rows]
        key = ("broadcast", n, str(dtype), int(root_pos), self.shard_mode)
        fn = self._cached(key, lambda: self._build_broadcast(root_pos))
        x = self._stage_rows(rows)
        out = fn(x)
        return self._fanout(self._replicated_out(out, dtype))

    def _build_broadcast(self, root_pos):
        def bcast_block(xb):
            g = lax.all_gather(xb, "hvd", axis=0, tiled=True)
            return g[root_pos]

        def bcast_stacked(x):
            return x[root_pos]

        if self.shard_mode:
            mapped = shard_map(
                bcast_block, mesh=self.mesh,
                in_specs=(P("hvd"),), out_specs=P(),
                check_vma=False)
            return jax.jit(mapped, donate_argnums=self._donate)
        return jax.jit(bcast_stacked, donate_argnums=self._donate)

    # -- alltoall -----------------------------------------------------------

    def alltoall(self, rows, splits, rest_shape):
        """``splits[r]`` is rank r's send-split vector (length R) over
        its first dimension — ALL ranks' splits (controller-negotiated).
        ``rows`` are per-local-rank padded buffers of shape
        (R * max_seg * rest,): segment j of rank r lives at
        [j*max_seg*rest : j*max_seg*rest + splits[r][j]*rest].
        Returns (per-local-rank received buffers, per-local-rank
        recv_splits).

        Skew: XLA collectives are static-shaped, so the one-shot
        ``all_to_all`` pads every segment to the GLOBAL max split —
        wire traffic R*max(split) instead of the exact byte counts the
        reference moves (mpi_operations.cc:441-530).  Balanced loads
        (MoE capacity-factor routing, even shards) pad ~nothing and
        take that path; when padding would more than double the wire
        bytes, the exchange switches to the DIAGONAL schedule — R-1
        ``ppermute`` steps, step d carrying only segment (r+d) padded
        to that diagonal's own max — so a single pathological split
        inflates one step, not every segment."""
        R = self.num_ranks
        dtype = rows[0].dtype
        rest = int(np.prod(rest_shape, dtype=np.int64)) if rest_shape else 1
        max_seg = max((s for split in splits for s in split), default=0)
        recv_splits_all = [[splits[j][r] for j in range(R)]
                           for r in range(R)]
        recv_local = [recv_splits_all[pos] for pos in self.local_positions]
        if max_seg == 0 or rest == 0:
            empty = np.zeros((0,) + tuple(rest_shape), dtype=dtype)
            return [empty.copy() for _ in self.local_positions], recv_local
        diag_max = [max(splits[r][(r + d) % R] for r in range(R))
                    for d in range(R)]
        # schedule pick: the diagonal path once one-shot padding
        # inflates wire bytes >1.25x (a threshold chosen on the
        # virtual CPU mesh at R=8, not measured on a chip;
        # docs/benchmarks.md "Alltoall padding").
        # HOROVOD_TPU_ALLTOALL_SCHEDULE={auto,oneshot,diag} forces it
        # for experiments.
        from ..common import env as env_mod
        mode = env_mod.get_str(
            env_mod.HOROVOD_TPU_ALLTOALL_SCHEDULE, "auto")
        if mode not in ("auto", "oneshot", "diag"):
            raise ValueError(
                f"HOROVOD_TPU_ALLTOALL_SCHEDULE={mode!r}: must be "
                f"'auto', 'oneshot', or 'diag'")
        want_diag = (mode == "diag" or
                     (mode == "auto" and
                      4 * R * max_seg > 5 * sum(diag_max)))
        if self.shard_mode and R > 2 and want_diag:
            return self._alltoall_diag(rows, splits, rest_shape,
                                       diag_max, recv_local)
        m = max_seg * rest
        key = ("alltoall", R, m, str(dtype), self.shard_mode)
        fn = self._cached(key, lambda: self._build_alltoall(m))
        x = self._stage_rows([self._pad_segments(r, splits[pos], m, rest)
                              for r, pos in zip(rows,
                                                self.local_positions)])
        out = fn(x)  # (R_dst, R*m) sharded by dst; row r = segments recv'd
        padded_rows = self._rows_out(out, dtype)
        results = []
        for i, pos in enumerate(self.local_positions):
            segs = [
                padded_rows[i][j * m: j * m + recv_local[i][j] * rest]
                for j in range(R)
            ]
            buf = np.concatenate(segs) if segs else np.zeros(0, dtype=dtype)
            results.append(buf.reshape((-1,) + tuple(rest_shape)))
        return results, recv_local

    def _pad_segments(self, flat, my_splits, m, rest):
        """Exact concat buffer -> per-destination padded layout."""
        R = self.num_ranks
        buf = np.zeros(R * m, dtype=flat.dtype)
        off = 0
        for j in range(R):
            seg = my_splits[j] * rest
            buf[j * m: j * m + seg] = flat[off:off + seg]
            off += seg
        return buf

    def _alltoall_diag(self, rows, splits, rest_shape, diag_max,
                       recv_local):
        """Skew-aware alltoall: one ppermute per diagonal ``d`` (rank
        r -> rank (r+d) % R), each padded only to that diagonal's max
        segment.  Total wire = sum(diag_max) vs the one-shot path's
        R * max(split)."""
        R = self.num_ranks
        dtype = rows[0].dtype
        rest = int(np.prod(rest_shape, dtype=np.int64)) if rest_shape else 1
        ms = [dm * rest for dm in diag_max]
        key = ("alltoall_diag", R, tuple(ms), str(dtype))
        fn = self._cached(key, lambda: self._build_alltoall_diag(ms))
        staged = []
        for d in range(R):
            diag_rows = []
            for flat, pos in zip(rows, self.local_positions):
                j = (pos + d) % R
                off = sum(splits[pos][:j]) * rest
                seg = splits[pos][j] * rest
                buf = np.zeros(max(ms[d], 1), dtype=dtype)
                buf[:seg] = flat[off:off + seg]
                diag_rows.append(buf)
            staged.append(self._stage_rows(diag_rows))
        outs = fn(*staged)
        # out d, row r = the segment sent by src (r-d) % R
        per_local_out = [self._rows_out(o, dtype) for o in outs]
        results = []
        for i, pos in enumerate(self.local_positions):
            segs = []
            for j in range(R):          # reassemble in src order
                d = (pos - j) % R
                seg = recv_local[i][j] * rest
                segs.append(per_local_out[d][i][:seg])
            buf = np.concatenate(segs) if segs else np.zeros(0, dtype=dtype)
            results.append(buf.reshape((-1,) + tuple(rest_shape)))
        return results, recv_local

    def _build_alltoall_diag(self, ms):
        R = self.num_ranks

        def body(*xs):
            outs = [xs[0]]              # d=0: own segment stays local
            for d in range(1, R):
                perm = [(r, (r + d) % R) for r in range(R)]
                outs.append(lax.ppermute(xs[d], "hvd", perm=perm))
            return tuple(outs)

        mapped = shard_map(
            body, mesh=self.mesh,
            in_specs=tuple(P("hvd") for _ in range(R)),
            out_specs=tuple(P("hvd") for _ in range(R)),
            check_vma=False)
        return jax.jit(mapped)

    def _build_alltoall(self, m):
        R = self.num_ranks

        def a2a_block(xb):
            # xb: (1, R*m) → (R, m): tiled all_to_all along axis 0 sends
            # row j to rank j and places the row received from rank j at
            # position j — exactly the recv-segment layout.
            x2 = xb.reshape(R, m)
            y = lax.all_to_all(x2, "hvd", split_axis=0, concat_axis=0,
                               tiled=True)
            return y.reshape(1, R * m)

        def a2a_stacked(x):
            # x: (R_src, R*m) → out[dst, src*m:..] = x[src, dst*m:..]
            x3 = x.reshape(R, R, m)
            return jnp.swapaxes(x3, 0, 1).reshape(R, R * m)

        if self.shard_mode:
            mapped = shard_map(
                a2a_block, mesh=self.mesh,
                in_specs=(P("hvd"),), out_specs=P("hvd"),
                check_vma=False)
            return jax.jit(mapped, donate_argnums=self._donate)
        return jax.jit(a2a_stacked, donate_argnums=self._donate)

    # -- reducescatter ------------------------------------------------------

    @staticmethod
    def chunk_sizes(d0, num_ranks):
        """Uneven reducescatter chunking: as even as possible, larger
        chunks on lower ranks (reference collective_operations.cc
        ReducescatterOp::ComputeOutputShapeForRank).  THE rule lives
        in core/sharded.py — the shard planner slices by it, so one
        definition keeps the plan and the scatter from drifting."""
        from ..core.sharded import chunk_sizes as _rule
        return _rule(d0, num_ranks)

    def reducescatter(self, rows, d0, rest_shape, op: ReduceOp,
                      prescale=1.0, postscale=1.0):
        """rows: per-local-rank buffers pre-placed into padded layout
        (R * max_chunk * rest,) where destination rank j's real rows
        sit at [j*max_chunk*rest ...].  Returns per-local-rank
        (chunk_j, *rest)."""
        R = self.num_ranks
        dtype = rows[0].dtype
        chunks = self.chunk_sizes(d0, R)
        max_chunk = max(chunks) if chunks else 0
        rest = int(np.prod(rest_shape, dtype=np.int64)) if rest_shape else 1
        if max_chunk == 0 or rest == 0:
            return [np.zeros((chunks[pos],) + tuple(rest_shape), dtype=dtype)
                    for pos in self.local_positions]
        is_float = _is_float(dtype)
        if is_float and op == ReduceOp.AVERAGE:
            postscale = postscale / R
            op = ReduceOp.SUM
        # int average/scaling: reference semantics (FP64 scale +
        # truncating cast; average divides) — see allreduce
        scaled = is_float or op == ReduceOp.AVERAGE or \
            prescale != 1.0 or postscale != 1.0
        key = ("reducescatter", R, max_chunk, rest, str(dtype), int(op),
               scaled, self.shard_mode)
        fn = self._cached(key, lambda: self._build_reducescatter(
            max_chunk, rest, dtype, op, scaled))
        x = self._stage_rows(rows)
        if scaled:
            sdt = _scale_np_dtype(dtype)
            out = fn(x, sdt(prescale), sdt(postscale))
        else:
            out = fn(x)
        per_local = self._rows_out(out, dtype)
        return [
            row[: chunks[pos] * rest].reshape(
                (chunks[pos],) + tuple(rest_shape))
            for row, pos in zip(per_local, self.local_positions)
        ]

    def _build_reducescatter(self, max_chunk, rest, dtype, op, scaled):
        R = self.num_ranks
        m = max_chunk * rest
        sf = _scale_jnp_dtype(dtype)
        avg_int = op == ReduceOp.AVERAGE
        if avg_int:
            op = ReduceOp.SUM

        def post_step(y, post):
            if avg_int:
                return ((y.astype(sf) / R) * post).astype(dtype)
            return (y.astype(sf) * post).astype(dtype)

        def rs_block(xb, pre, post):
            # xb: (1, R*m).  psum_scatter over tiles of m elements.
            if scaled:
                xb = (xb.astype(sf) * pre).astype(dtype)
            if op == ReduceOp.SUM:
                y = lax.psum_scatter(xb, "hvd", scatter_dimension=1,
                                     tiled=True)
            else:
                # MIN/MAX/PRODUCT reducescatter: gather then reduce the
                # local tile (no fused XLA primitive for these).
                g = lax.all_gather(xb, "hvd", axis=0, tiled=True)  # (R, R*m)
                idx = lax.axis_index("hvd")
                tile = lax.dynamic_slice(
                    g, (jnp.zeros((), jnp.int32),
                        (idx * m).astype(jnp.int32)), (R, m))
                if op == ReduceOp.MIN:
                    y = jnp.min(tile, axis=0, keepdims=True)
                elif op == ReduceOp.MAX:
                    y = jnp.max(tile, axis=0, keepdims=True)
                elif op == ReduceOp.PRODUCT:
                    y = jnp.prod(tile, axis=0, keepdims=True, dtype=tile.dtype)
                else:
                    raise ValueError(f"unsupported reducescatter op {op}")
            if scaled:
                y = post_step(y, post)
            return y

        def rs_stacked(x, pre, post):
            # x: (R, R*m) → out (R, m): out[j] = reduce_r x[r, j*m:(j+1)*m]
            if scaled:
                x = (x.astype(sf) * pre).astype(dtype)
            x = x.reshape(R, R, m)
            if op == ReduceOp.SUM:
                # dtype pinned: jnp.sum follows numpy's
                # promote-small-ints-to-default-int rule, which
                # would hand int32 callers int64 results
                y = jnp.sum(x, axis=0, dtype=x.dtype)
            elif op == ReduceOp.MIN:
                y = jnp.min(x, axis=0)
            elif op == ReduceOp.MAX:
                y = jnp.max(x, axis=0)
            elif op == ReduceOp.PRODUCT:
                y = jnp.prod(x, axis=0, dtype=x.dtype)
            else:
                raise ValueError(f"unsupported reducescatter op {op}")
            if scaled:
                y = post_step(y, post)
            return y

        if self.shard_mode:
            mapped = shard_map(
                rs_block, mesh=self.mesh,
                in_specs=(P("hvd"), P(), P()), out_specs=P("hvd"),
                check_vma=False)
            fn = jax.jit(mapped, donate_argnums=self._donate)
        else:
            fn = jax.jit(rs_stacked, donate_argnums=self._donate)
        if scaled:
            return fn
        return lambda x: fn(x, np.float32(1.0), np.float32(1.0))
