"""The chunked selective scan of a state-space layer (``models/mamba.py``)
as a pair of Pallas kernels under one ``jax.custom_vjp``.

The recurrence ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``, ``y_t =
S_t C_t + D x_t`` in the chunked form ``ssd_chunked`` spells out in XLA
operations, with the same numbers at the same places (cumulative sums,
decays and the carried state float32; every product's operands in the
activation dtype, accumulated in float32), but:

* ``ssd_fwd`` sweeps a row's chunks in order, grid ``(rows, blocks of
  heads, chunks)`` with the chunk axis innermost and sequential.  The
  state of the block's heads, ``(N, heads x P)`` float32, lives in VMEM
  scratch across the chunk axis and is zeroed at a row's first chunk.  A
  grid step reads its chunk of x, B, C in the flat ``(S, H x P)`` /
  ``(S, G x N)`` layout through the BlockSpec, computes ``C B^T`` once
  and the read of the carried state and the state's update for all of
  the block's heads in one product each (the MXU's full width), builds
  each head's ``(L, L)`` decay tile in VMEM (the exponent masked BEFORE
  the exp, every exponent <= 0), multiplies
  by the scores and by dt, casts ONCE, runs the masked product against x
  itself (``ssd_chunked`` casts the tile and ``dt x`` each: one rounding
  fewer), adds the read of the state and the ``D`` skip, writes y once
  in the activation dtype, and writes the state the chunk STARTED from
  for the backward.  The decays never see HBM.
* ``ssd_bwd`` sweeps the chunks in reverse under the same grid, the
  state's gradient in scratch, rebuilding ``B C^T`` and the decay tile
  (transposed, so that no product needs a per-head transpose), and
  yields dx, per-block partial sums of dB and dC, the skip's gradient
  lane by lane, and the gradients of ``dt`` and of the log decays per
  position and head.  Cotangents enter its products as they arrive, in
  the activation dtype, and what it sums stays float32.
* Heads of P < 128 are taken in groups that fill a 128-lane tile: what
  is ``(rows, P)`` wide is computed for the group at once and a head's
  share of a product over the group's lanes is selected from it, so no
  operand is read at a lane offset inside a tile.
* Everything of size ``(B, S, H)`` stays XLA (a few MB a layer): the
  cumulative sum inside a chunk, the decays derived from it per
  position, their transposes into the two orientations the kernels read
  (a head's positions down the sublanes, and along the lanes), and the
  sums that finish dB, dC and dD.

``ssd_chunked`` stays the reference and the path for shapes the kernels
do not take (``kernel_takes``): the choice is a function of shapes and
dtype alone.  ``interpret=None`` follows
``pallas_kernels.default_interpret()``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_kernels
from .pallas_kernels import _named_kernel

#: the name the states the chunks start from are checkpointed under:
#: every remat policy of a model with mamba layers keeps it beside the
#: scan's output (``transformer._with_remat``), so a replay runs no
#: ``ssd_fwd``
KEPT_STATES = "ssd_states"

# what the kernels may ask of VMEM: the compiler's default scoped limit
# is 16 MiB of a core's 128; a step holds ~9 MB at 16 heads a block
_VMEM_LIMIT_BYTES = 64 << 20


def heads_per_block(heads, groups, width):
    """The heads a grid step takes: the largest of 16, 8 that divides a
    group's heads and fills whole 128-lane tiles; ``None`` where none
    does."""
    each = heads // groups
    for n in (16, 8):
        if each % n == 0 and (n * width) % 128 == 0:
            return n
    return None


def kernel_takes(x_shape, bc_shape, chunk, dtype):
    """Whether the kernel pair runs a scan of these shapes: a chunk
    length, a state size and a block of heads that fill whole tiles
    (``x`` (B, S, H, P), ``b`` / ``c`` (B, S, G, N))."""
    _, seq, heads, width = x_shape
    groups, state = bc_shape[-2:]
    return (min(chunk, seq) % 128 == 0 and state % 128 == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32))
            and heads_per_block(heads, groups, width) is not None)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[0], contract[1]), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return _dot(a, b, ((1,), (0,)))


def _nt(a, b):
    return _dot(a, b, ((1,), (1,)))


def _tn(a, b):
    return _dot(a, b, ((0,), (0,)))


def _causal(length, later, earlier):
    """(L, L) bool: the position along axis ``later`` is not before the
    one along axis ``earlier``."""
    shape = (length, length)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, later)
            >= jax.lax.broadcasted_iota(jnp.int32, shape, earlier))


def _lane_groups(heads, width):
    """The block's heads in groups that fill a 128-lane tile (two heads
    of 64): ``(heads a group, groups)``.  What is (rows, P) wide is
    computed for a whole group at once, and a head's share of a product
    over the group's lanes is selected from it, so no operand is read at
    a lane offset inside a tile."""
    each = max(1, min(heads, 128 // width))
    while heads % each:
        each -= 1
    return each, heads // each


def _by_head(head_of_lane, values):
    """(rows, lanes) from one (rows, lanes) or (rows, 1) value a head:
    lane j takes the value of the head it belongs to."""
    out = values[-1]
    for i in range(len(values) - 2, -1, -1):
        out = jnp.where(head_of_lane == i, values[i], out)
    return out


def _fwd_kernel(x_ref, bt_ref, c_ref, cumc_ref, wc_ref, cumr_ref, dtr_ref,
                kept_ref, skip_ref, y_ref, starts_ref, state, scores,
                from_start, v_all, *, heads, width):
    """One chunk of one block of heads.  x_ref / y_ref: (1, L, heads x
    P); c_ref: (1, L, N); bt_ref: (1, N, L); cumc_ref / wc_ref: (1, 1, L,
    heads) (a head's positions down the sublanes: the log decay from
    the chunk's start up to and with a position; dt times the decay
    from the position to the chunk's end); cumr_ref / dtr_ref: (1,
    heads, L) (positions along the lanes: that log decay, and dt);
    kept_ref: (1, 1, 1, heads x P) (what a head keeps of its state over
    the whole chunk, on each of its lanes); skip_ref: (1, 1, heads x P);
    starts_ref: (1, 1, N, heads x P).  Scratch: state (N, heads x P),
    scores (L, L) and from_start (L, heads x P) float32; v_all (L, heads
    x P) in the activation dtype."""
    length = x_ref.shape[1]
    dtype = x_ref.dtype
    f32 = jnp.float32
    each, groups = _lane_groups(heads, width)
    wide = each * width

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    start = state[...]
    starts_ref[0, 0] = start
    c = c_ref[0]
    scores[...] = _nn(c, bt_ref[0])                     # (l, s)
    from_start[...] = _nn(c, start.astype(dtype))       # (l, heads x P)
    causal = _causal(length, 0, 1)                      # l reads s <= l
    head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (length, wide),
                                            1) // width
    for g in range(groups):
        lanes = slice(g * wide, (g + 1) * wide)
        members = range(g * each, (g + 1) * each)
        products, cums = [], []
        for h in members:
            # ONE broadcast along the lanes for the tile and for the
            # read of the carried state
            cum_l = jnp.broadcast_to(cumc_ref[0, 0, :, h:h + 1],
                                     (length, max(length, wide)))
            decay = jnp.exp(jnp.where(
                causal, cum_l[:, :length] - cumr_ref[0, h:h + 1, :],
                -jnp.inf))
            # dt rides on the tile: x itself is the product's operand
            tile = (scores[...] * decay * dtr_ref[0, h:h + 1, :]
                    ).astype(dtype)
            products.append(_nn(tile, x_ref[0, :, lanes]))
            cums.append(cum_l[:, :wide])
        x = x_ref[0, :, lanes].astype(f32)
        y = _by_head(head_of_lane, products)
        y = y + from_start[:, lanes] * jnp.exp(_by_head(head_of_lane, cums))
        y = y + skip_ref[0, :, lanes] * x
        y_ref[0, :, lanes] = y.astype(dtype)
        v_all[:, lanes] = (x * _by_head(head_of_lane, [
            wc_ref[0, 0, :, h:h + 1] for h in members])).astype(dtype)
    # what the chunk keeps of the state it started from, and what it
    # adds to it by its end
    state[...] = start * kept_ref[0, 0] + _nn(bt_ref[0], v_all[...])


def _bwd_kernel(x_ref, dy_ref, b_ref, bt_ref, c_ref, ct_ref, dtc_ref,
                cumc_ref, toc_ref, cumr_ref, kept_ref, skip_ref, starts_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dcumc_ref, dtailc_ref,
                dcumr_ref, dkept_ref, dskip_ref,
                dstate, scores_t, from_start, dv_all, v_all, dyd_all,
                dscores, *, heads, width):
    """One chunk of one block of heads, the chunks visited last first.
    ``dstate`` (N, heads x P) float32 is the gradient of the state the
    chunk ENDS with.  Tiles are held transposed, (s, l): position l
    reads s <= l.  Besides ``_fwd_kernel``'s operands: dy_ref as x_ref;
    b_ref as c_ref, ct_ref as bt_ref; dtc_ref / toc_ref as cumc_ref (dt,
    and the decay from a position to the chunk's end).  Outputs: dx_ref as
    x_ref, db_ref / dc_ref (1, 1, L, N) float32 (this block of heads'
    share), ddt_ref / dcumc_ref / dtailc_ref as cumc_ref (the gradients
    of dt, of the log decay up to a position, and of the log decay from
    it to the end), dcumr_ref as cumr_ref (the log decay's gradient
    again, the share that falls along the lanes: the two are summed
    outside), dkept_ref as kept_ref, dskip_ref (1, 1, heads x P) (the
    skip's gradient, lane by lane, summed over the row's chunks)."""
    length = x_ref.shape[1]
    dtype = x_ref.dtype
    f32 = jnp.float32
    each, groups = _lane_groups(heads, width)
    wide = each * width

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dskip_ref[...] = jnp.zeros_like(dskip_ref)

    b, c = b_ref[0], c_ref[0]
    start = starts_ref[0, 0]
    start_lp = start.astype(dtype)
    dend = dstate[...]
    dend_lp = dend.astype(dtype)
    scores_t[...] = _nn(b, ct_ref[0])                   # (s, l)
    from_start[...] = _nn(c, start_lp)                  # (l, heads x P)
    dv_all[...] = _nn(b, dend_lp)                       # (s, heads x P)
    causal_t = _causal(length, 1, 0)                    # s is read by l >= s
    head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (length, wide),
                                            1) // width
    for g in range(groups):
        lanes = slice(g * wide, (g + 1) * wide)
        members = range(g * each, (g + 1) * each)

        def columns(ref):
            return _by_head(head_of_lane,
                            [ref[0, 0, :, h:h + 1] for h in members])

        dt, to_end = columns(dtc_ref), columns(toc_ref)
        x = x_ref[0, :, lanes].astype(f32)
        dy = dy_ref[0, :, lanes].astype(f32)
        xdt = x * dt
        # ONE broadcast along the lanes for the tile and for the read of
        # the carried state
        cums = [jnp.broadcast_to(cumc_ref[0, 0, :, h:h + 1],
                                 (length, max(length, wide)))
                for h in members]
        dyd = dy * jnp.exp(_by_head(head_of_lane,
                                    [t[:, :wide] for t in cums]))
        dskip_ref[0, :, lanes] += jnp.sum(dy * x, axis=0, keepdims=True)
        from_read = dyd * from_start[:, lanes]
        dv = dv_all[:, lanes]
        to_state = xdt * to_end
        from_state = dv * to_state
        dxdts = []
        for i, h in enumerate(members):
            mine = head_of_lane == i
            column = slice(h, h + 1)
            decay_t = jnp.exp(jnp.where(
                causal_t, cumr_ref[0, h:h + 1, :] - cums[i][:, :length],
                -jnp.inf))
            held = scores_t[...]
            dxdts.append(_nn((held * decay_t).astype(dtype),
                             dy_ref[0, :, lanes]))
            through = _nt(jnp.where(mine, xdt, 0.0).astype(dtype),
                          dy_ref[0, :, lanes]) * decay_t
            if h:
                dscores[...] += through                 # (s, l): d scores
            else:
                dscores[...] = through
            dlog = through * held                       # d (cum_l - cum_s)
            dcumr_ref[0, h:h + 1, :] = jnp.sum(dlog, axis=0, keepdims=True)
            # a position's own share: inside the chunk, and the read of
            # the state the chunk started from
            if length % wide:
                dcum_s = jnp.sum(jnp.where(mine, from_read, 0.0), axis=1,
                                 keepdims=True) \
                    - jnp.sum(dlog, axis=1, keepdims=True)
            else:           # one sum along the lanes for the two
                dcum_s = jnp.sum(jnp.where(mine, from_read, 0.0) - sum(
                    dlog[:, j:j + wide] for j in range(0, length, wide)),
                    axis=1, keepdims=True)
            dcumc_ref[0, 0, :, column] = dcum_s
            dtailc_ref[0, 0, :, column] = jnp.sum(
                jnp.where(mine, from_state, 0.0), axis=1, keepdims=True)
        dxdt = _by_head(head_of_lane, dxdts) + dv * to_end
        dyd_all[:, lanes] = dyd.astype(dtype)
        v_all[:, lanes] = to_state.astype(dtype)
        dx_ref[0, :, lanes] = (dxdt * dt + skip_ref[0, :, lanes] * dy
                               ).astype(dtype)
        to_dt = dxdt * x
        for i, h in enumerate(members):
            ddt_ref[0, 0, :, h:h + 1] = jnp.sum(
                jnp.where(head_of_lane == i, to_dt, 0.0), axis=1,
                keepdims=True)
    dkept_ref[0, 0] = jnp.sum(dend * start, axis=0, keepdims=True)
    dstate[...] = dend * kept_ref[0, 0] + _nn(ct_ref[0], dyd_all[...])
    dscores_lp = dscores[...].astype(dtype)
    db_ref[0, 0] = _nn(dscores_lp, c) + _nt(v_all[...], dend_lp)
    dc_ref[0, 0] = _tn(dscores_lp, b) + _nt(dyd_all[...], start_lp)


def _operands(x, dt, cum, tail, end, b, skip, heads, groups, length, n):
    """The small operands in the kernels' layouts, the BlockSpecs of all
    of them and the sizes, shared by both kernels.  ``specs(chunk_of)``:
    ``chunk_of`` turns the grid's innermost index into the chunk it
    visits (the backward's sweep is reversed)."""
    rows, seq, inner = x.shape
    width = inner // heads
    each = heads // groups
    blocks = heads // n
    state = b.shape[-1] // groups
    chunks = seq // length

    def by_block(t):        # (B, S, H) -> (B, blocks, S, n)
        return jnp.moveaxis(t.reshape(rows, seq, blocks, n), 2, 1)

    to_end = jnp.exp(tail)
    small = dict(
        dtc=by_block(dt), cumc=by_block(cum), toc=by_block(to_end),
        wc=by_block(dt * to_end),
        cumr=_transposed(cum), dtr=_transposed(dt),
        kept=jnp.repeat(jnp.exp(end), width, axis=-1)[:, :, None],
        skip=jnp.repeat(skip, width).reshape(blocks, 1, n * width))

    def specs(chunk_of):
        def group(j):
            return (j * n) // each
        return dict(
            x=pl.BlockSpec((1, length, n * width),
                           lambda r, j, k: (r, chunk_of(k), j)),
            bc=pl.BlockSpec((1, length, state),
                            lambda r, j, k: (r, chunk_of(k), group(j))),
            bct=pl.BlockSpec((1, state, length),
                             lambda r, j, k: (r, group(j), chunk_of(k))),
            col=pl.BlockSpec((1, 1, length, n),
                             lambda r, j, k: (r, j, chunk_of(k), 0)),
            row=pl.BlockSpec((1, n, length),
                             lambda r, j, k: (r, j, chunk_of(k))),
            kept=pl.BlockSpec((1, 1, 1, n * width),
                              lambda r, j, k: (r, chunk_of(k), 0, j)),
            skip=pl.BlockSpec((1, 1, n * width), lambda r, j, k: (j, 0, 0)),
            by_row=pl.BlockSpec((1, 1, n * width), lambda r, j, k: (r, 0, j)),
            starts=pl.BlockSpec((1, 1, state, n * width),
                                lambda r, j, k: (r, chunk_of(k), 0, j)),
            part=pl.BlockSpec((1, 1, length, state),
                              lambda r, j, k: (r, j, chunk_of(k), 0)))
    dims = dict(rows=rows, seq=seq, inner=inner, width=width, blocks=blocks,
                chunks=chunks, state=state)
    return small, specs, dims


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _transposed(t):
    return jnp.moveaxis(t, 1, 2)


# jitted: the layers of a model (and a step's forward and replay) trace
# and lower ONE scan of a shape, not one a call
@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11, 12))
def _fwd_call(x, dt, cum, tail, end, b, c, skip, heads, groups, length, n,
              interpret):
    small, specs, d = _operands(x, dt, cum, tail, end, b, skip, heads,
                                groups, length, n)
    s = specs(lambda k: k)
    wide = n * d["width"]
    return _named_kernel(
        "ssd_fwd",
        functools.partial(_fwd_kernel, heads=n, width=d["width"]),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(
                       (d["rows"], d["chunks"], d["state"], d["inner"]),
                       jnp.float32)),
        grid=(d["rows"], d["blocks"], d["chunks"]),
        in_specs=[s["x"], s["bct"], s["bc"], s["col"], s["col"], s["row"],
                  s["row"], s["kept"], s["skip"]],
        out_specs=(s["x"], s["starts"]),
        scratch_shapes=[pltpu.VMEM((d["state"], wide), jnp.float32),
                        pltpu.VMEM((length, length), jnp.float32),
                        pltpu.VMEM((length, wide), jnp.float32),
                        pltpu.VMEM((length, wide), x.dtype)],
        compiler_params=_params(),
        interpret=interpret,
    )(x, _transposed(b), c, small["cumc"], small["wc"], small["cumr"],
      small["dtr"], small["kept"], small["skip"])


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11, 12))
def _scan(x, dt, cum, tail, end, b, c, skip, heads, groups, length, n,
          interpret):
    """x (B, S, H x P), b / c (B, S, G x N), S a multiple of ``length``;
    dt, cum, tail (B, S, H) and end (B, chunks, H) float32: a position's
    dt, the log decay from its chunk's start up to and with it and from
    it to the chunk's end, and the whole chunk's; skip (H,).  ``n``
    heads a grid step."""
    return _fwd_call(x, dt, cum, tail, end, b, c, skip, heads, groups,
                     length, n, interpret)[0]


def _scan_fwd(x, dt, cum, tail, end, b, c, skip, heads, groups, length, n,
              interpret):
    y, starts = _fwd_call(x, dt, cum, tail, end, b, c, skip, heads, groups,
                          length, n, interpret)
    # the residuals are the scan's inputs and the states, which a remat
    # policy keeps by name: a replay then runs no ssd_fwd at all
    return y, (x, dt, cum, tail, end, b, c, skip,
               checkpoint_name(starts, KEPT_STATES))


def _scan_bwd(heads, groups, length, n, interpret, res, dy):
    return _bwd_call(*res, dy, heads, groups, length, n, interpret)


@functools.partial(jax.jit, static_argnums=(10, 11, 12, 13, 14))
def _bwd_call(x, dt, cum, tail, end, b, c, skip, starts, dy, heads, groups,
              length, n, interpret):
    small, specs, d = _operands(x, dt, cum, tail, end, b, skip, heads,
                                groups, length, n)
    last = d["chunks"] - 1
    s = specs(lambda k: last - k)
    f32 = jnp.float32
    wide = n * d["width"]
    by_block = (d["rows"], d["blocks"], d["seq"])
    dx, db, dc, ddt, dcumc, dtailc, dcumr, dkept, dskip = _named_kernel(
        "ssd_bwd",
        functools.partial(_bwd_kernel, heads=n, width=d["width"]),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(by_block + (d["state"],), f32),
                   jax.ShapeDtypeStruct(by_block + (d["state"],), f32),
                   jax.ShapeDtypeStruct(by_block + (n,), f32),
                   jax.ShapeDtypeStruct(by_block + (n,), f32),
                   jax.ShapeDtypeStruct(by_block + (n,), f32),
                   jax.ShapeDtypeStruct((d["rows"], heads, d["seq"]), f32),
                   jax.ShapeDtypeStruct(small["kept"].shape, f32),
                   jax.ShapeDtypeStruct((d["rows"], 1, d["inner"]), f32)),
        grid=(d["rows"], d["blocks"], d["chunks"]),
        in_specs=[s["x"], s["x"], s["bc"], s["bct"], s["bc"], s["bct"],
                  s["col"], s["col"], s["col"], s["row"], s["kept"],
                  s["skip"], s["starts"]],
        out_specs=(s["x"], s["part"], s["part"], s["col"], s["col"],
                   s["col"], s["row"], s["kept"], s["by_row"]),
        scratch_shapes=[pltpu.VMEM((d["state"], wide), f32),
                        pltpu.VMEM((length, length), f32),
                        pltpu.VMEM((length, wide), f32),
                        pltpu.VMEM((length, wide), f32),
                        pltpu.VMEM((length, wide), x.dtype),
                        pltpu.VMEM((length, wide), x.dtype),
                        pltpu.VMEM((length, length), f32)],
        compiler_params=_params(),
        interpret=interpret,
    )(x, dy, b, _transposed(b), c, _transposed(c), small["dtc"],
      small["cumc"], small["toc"], small["cumr"], small["kept"],
      small["skip"], starts)

    def flat(t):            # (B, blocks, S, n) -> (B, S, H)
        return jnp.moveaxis(t, 1, 2).reshape(d["rows"], d["seq"], heads)

    def by_group(t):        # (B, blocks, S, N) -> (B, S, G x N)
        t = t.reshape(d["rows"], groups, -1, d["seq"], d["state"])
        return jnp.moveaxis(t.sum(2), 1, 2).reshape(b.shape).astype(b.dtype)

    dend = jnp.exp(end) * dkept.reshape(
        d["rows"], d["chunks"], heads, d["width"]).sum(-1)
    dskip = dskip.reshape(-1, heads, d["width"]).sum((0, 2))
    return (dx, flat(ddt), flat(dcumc) + _transposed(dcumr), flat(dtailc),
            dend, by_group(db), by_group(dc), dskip)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, a, b, c, skip, *, chunk, block_heads=None,
             interpret=None):
    """``ssd_chunked`` with the ``D`` skip, through the kernel pair:
    ``y_t = S_t C_t + skip x_t``.

    x: (B, S, H, P) and b, c: (B, S, G, N) in the activation dtype; dt:
    (B, S, H) float32, positive; a, skip: (H,) float32.  Returns ``(y,
    chunks)``: y (B, S, H, P) in x's dtype and the number of chunks a
    row took.  ``block_heads`` (the heads a grid step takes) is for the
    tests; the default is ``heads_per_block``'s."""
    rows, seq, heads, width = x.shape
    groups, state = b.shape[-2:]
    if interpret is None:
        interpret = pallas_kernels.default_interpret()
    length = min(chunk, seq)
    # a row that ends inside a chunk is filled up with positions of
    # dt = 0: they leave the state as it is and add nothing to it
    fill = -seq % length
    if fill:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, fill)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    chunks, filled = (seq + fill) // length, seq + fill
    # log decay from a chunk's start up to and with each of its
    # positions, over the whole chunk, and from a position to the
    # chunk's end: float32, every one <= 0
    cum = jnp.cumsum((dt * a).reshape(rows, chunks, length, heads), axis=2)
    end = cum[:, :, -1]
    tail = (end[:, :, None] - cum).reshape(dt.shape)
    y = _scan(x.reshape(rows, filled, heads * width), dt,
              cum.reshape(dt.shape), tail, end,
              b.reshape(rows, filled, groups * state),
              c.reshape(rows, filled, groups * state), skip, heads, groups,
              length, block_heads or heads_per_block(heads, groups, width),
              interpret)
    return y.reshape(rows, filled, heads, width)[:, :seq], chunks
