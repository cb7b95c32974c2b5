"""Expert parallelism: capacity-factor token routing over the fused
quantized alltoall.

models/transformer.py's ``MoE`` routes with dense one-hot einsums —
every token visits every expert's weights, which is fine at small E
but carries O(E) FLOPs per token and gives the wire nothing to
exchange.  This module is the FIXED-CAPACITY formulation (Switch /
GShard style): tokens are scattered into per-expert slots of a static
size, overflow is DROPPED deterministically, underflow is zero-padded
— so the dispatched tensor's shape never depends on the routing and
the compiled step never recompiles as the router drifts.  The static
(E, C, M) layout is also exactly what the alltoall wire wants: equal
splits, so the exchange rides ``CompiledAlltoall`` (host path) or
:func:`quantized_all_to_all` (in-graph, shard_map over the ``ep``
mesh axis) with the block-scaled int8/int4 codec fused in.

Determinism contract (tests/test_moe.py): same logits -> same routes,
same drops.  ``lax.top_k`` breaks ties by lowest index; slot
priority is token-major (token t's k-th choice outranks token t+1's
first), so "which token overflows" is a pure function of the logits
— never of scheduling.

The autotuner's TENTH dimension sweeps (ep, capacity factor) as one
categorical (:data:`MOE_CHOICES`, core/autotune.py): ep trades
alltoall fan-out against experts hosted per rank, the capacity factor
trades dropped tokens against padded exchange bytes — both move the
same wire, so they sweep together.
"""

from functools import lru_cache, partial

import numpy as np

import jax
import jax.custom_batching
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

__all__ = [
    "MOE_EP_CHOICES", "MOE_CF_CHOICES", "MOE_CHOICES", "moe_label",
    "parse_moe_label", "snap_ep", "expert_capacity", "top_k_gating",
    "make_dispatch_plan", "straight_through", "moe_dispatch",
    "moe_combine", "capacity_moe_apply", "quantized_all_to_all",
    "dense_flop_matched_ff", "score_top_k_routing",
    "softmax_top_k_routing", "route", "load_balance_loss",
    "updated_expert_bias", "held_buffer_rows", "routed_experts_apply",
]

#: expert-parallel degrees the autotuner sweeps (snapped at latch
#: time to a divisor of the process-set size by :func:`snap_ep`)
MOE_EP_CHOICES = (1, 2, 4, 8)

#: capacity factors the autotuner sweeps: 1.0 = exact budget (hot
#: experts drop), 1.5 = 50% headroom (cold experts pad the wire)
MOE_CF_CHOICES = (1.0, 1.25, 1.5)

#: the autotuner's TENTH dimension: (ep, capacity factor) as ONE
#: categorical — a legal-pair enumeration like schedule.PP_CHOICES,
#: swept by core/autotune.py only when the job hosts experts
MOE_CHOICES = tuple(
    (ep, cf) for ep in MOE_EP_CHOICES for cf in MOE_CF_CHOICES)


def moe_label(ep, cf):
    """Human/metric spelling of the autotune pair (the ``experts``
    label on ``horovod_autotune_best_config``)."""
    return f"ep{int(ep)}xcf{float(cf):g}"


def parse_moe_label(label):
    """Inverse of :func:`moe_label` -> (ep, capacity_factor)."""
    body = label.strip().lower()
    if not body.startswith("ep") or "xcf" not in body:
        raise ValueError(f"not a moe label: {label!r}")
    ep_s, cf_s = body[2:].split("xcf", 1)
    return int(ep_s), float(cf_s)


def snap_ep(ep, world_size):
    """Largest divisor of ``world_size`` that is <= max(ep, 1): the
    sweep may propose any grid degree; the layer latches a legal one
    (ep must divide the set so every rank hosts the same number of
    experts — the equal-splits contract of the alltoall wire)."""
    ep = max(int(ep or 1), 1)
    world_size = max(int(world_size), 1)
    best = 1
    for d in range(1, min(ep, world_size) + 1):
        if world_size % d == 0:
            best = d
    return best


def expert_capacity(n_tokens, num_experts, topk, capacity_factor):
    """Per-expert slot count: ``ceil(cf * tokens * topk / experts)``
    — the static shape that makes routing drift invisible to XLA."""
    if num_experts < 1:
        raise ValueError("num_experts must be >= 1")
    slots = float(capacity_factor) * int(n_tokens) * int(topk)
    return max(int(-(-slots // num_experts)), 1)


def top_k_gating(logits, topk):
    """Deterministic top-k router: softmax over ALL experts, take the
    k largest, renormalize among the selected.

    Returns ``(weights, idx)``, both ``(..., topk)``.  The selection
    is non-differentiable; gradients reach the router logits only
    through the selected weights — the straight-through estimator for
    the discrete choice (the combine applies it, see
    :func:`moe_combine`)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, idx = lax.top_k(probs, topk)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, idx


def make_dispatch_plan(idx, num_experts, capacity):
    """Slot assignment for flat routed choices ``idx`` (T, K).

    Returns ``(pos, keep, n_dropped)``: ``pos`` (T, K) int32 is each
    choice's slot within its expert, ``keep`` (T, K) bool marks the
    choices that fit under ``capacity``, ``n_dropped`` counts the
    overflow (the drop-accounting scalar tests and telemetry read).
    Priority is token-major: flatten (t, k) in t-major order and take
    a running count per expert — fully deterministic."""
    T, K = idx.shape
    flat = idx.reshape(T * K)
    oh = jax.nn.one_hot(flat, num_experts, dtype=jnp.int32)  # (TK, E)
    # position of each choice inside its expert's arrival order
    pos = (jnp.cumsum(oh, axis=0) - 1)
    pos = jnp.sum(pos * oh, axis=-1)                          # (TK,)
    keep = pos < capacity
    n_dropped = jnp.sum(~keep).astype(jnp.int32)
    return (pos.reshape(T, K).astype(jnp.int32),
            keep.reshape(T, K), n_dropped)


@jax.custom_vjp
def straight_through(weights, keep):
    """``weights * keep`` forward; identity-to-``weights`` backward.

    The keep mask is a step function of the routing order —
    d(keep)/d(weights) is zero a.e., which would starve the router of
    gradient exactly for the hot experts it most needs to cool.  The
    straight-through VJP passes the combine cotangent to ``weights``
    as if every choice had fit."""
    return weights * keep.astype(weights.dtype)


def _st_fwd(weights, keep):
    return weights * keep.astype(weights.dtype), None


def _st_bwd(_res, g):
    return g, None


straight_through.defvjp(_st_fwd, _st_bwd)


def moe_dispatch(x, idx, pos, keep, num_experts, capacity):
    """Scatter tokens ``x`` (T, M) into the static slot tensor
    ``(E, C, M)``: kept choice (t, k) lands at
    ``[idx[t,k], pos[t,k]]``; dropped choices vanish; empty slots are
    zero (the deterministic pad)."""
    T, M = x.shape
    K = idx.shape[1]
    keep_f = keep.reshape(T * K, 1).astype(x.dtype)
    slot = (idx.reshape(T * K) * capacity
            + jnp.minimum(pos.reshape(T * K), capacity - 1))
    out = jnp.zeros((num_experts * capacity, M), dtype=x.dtype)
    vals = jnp.repeat(x, K, axis=0) * keep_f
    # kept slots are unique by construction; dropped rows add zeros
    out = out.at[slot].add(vals)
    return out.reshape(num_experts, capacity, M)


def moe_combine(expert_out, idx, pos, keep, weights):
    """Gather expert outputs back to token order and mix:
    ``y[t] = sum_k st(w)[t,k] * out[idx[t,k], pos[t,k]]``.  Dropped
    choices contribute zero (their residual path carries the token);
    the router still sees their gradient through
    :func:`straight_through`."""
    E, C, M = expert_out.shape
    T, K = idx.shape
    flat = expert_out.reshape(E * C, M)
    slot = (idx.reshape(T * K) * C
            + jnp.minimum(pos.reshape(T * K), C - 1))
    gathered = flat[slot].reshape(T, K, M)
    gathered = gathered * keep.reshape(T, K, 1).astype(flat.dtype)
    w = straight_through(weights, keep).astype(flat.dtype)
    return jnp.einsum("tk,tkm->tm", w, gathered)


def capacity_moe_apply(x, router_w, wi_gate, wi_up, wo, *, topk,
                       capacity_factor, axis_name=None, wire=None):
    """One fixed-capacity MoE FFN: route -> dispatch -> (alltoall)
    -> SwiGLU experts -> (alltoall) -> combine.

    ``x`` (T, M); ``router_w`` (M, E); expert weights carry a leading
    E axis (``wi_*`` (E, M, F), ``wo`` (E, F, M) — shard them on the
    ``ep`` mesh axis).  With ``axis_name`` (inside shard_map over the
    ep axis) the dispatched slots cross ranks through
    :func:`quantized_all_to_all` — the wire-quantized exchange — and
    E is the LOCAL expert count; without it the layer is the
    single-rank reference.  Returns ``(y, aux)`` where ``aux`` has
    ``n_dropped`` and ``capacity``."""
    T, M = x.shape
    E = router_w.shape[-1]
    ep = lax.psum(1, axis_name) if axis_name is not None else 1
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    weights, idx = top_k_gating(logits, topk)
    cap = expert_capacity(T, E * ep, topk, capacity_factor)
    pos, keep, n_dropped = make_dispatch_plan(idx, E * ep, cap)
    slots = moe_dispatch(x, idx, pos, keep, E * ep, cap)  # (E*ep,C,M)
    if axis_name is not None:
        # (ep, E, C, M) by destination rank -> exchanged: this rank's
        # E experts receive every rank's C-slot slices
        ex = quantized_all_to_all(
            slots.reshape(ep, E * cap * M), axis_name, wire=wire)
        slots = ex.reshape(ep, E, cap, M).swapaxes(0, 1) \
            .reshape(E, ep * cap, M)
    gate = jax.nn.silu(jnp.einsum("ecm,emf->ecf", slots, wi_gate))
    up = jnp.einsum("ecm,emf->ecf", slots, wi_up)
    out = jnp.einsum("ecf,efm->ecm", gate * up, wo)
    if axis_name is not None:
        back = out.reshape(E, ep, cap, M).swapaxes(0, 1) \
            .reshape(ep, E * cap * M)
        out = quantized_all_to_all(back, axis_name, wire=wire) \
            .reshape(ep * E, cap, M)
    y = moe_combine(out, idx, pos, keep, weights).astype(x.dtype)
    return y, {"n_dropped": n_dropped, "capacity": cap}


# ---------------------------------------------------------------------------
# the dropless routed layer that is told which experts it holds

def score_top_k_routing(x, router_w, expert_bias, topk, *, route_scale=1.0):
    """Sigmoid scores over ALL experts in float32, the ``topk`` largest
    of ``scores + expert_bias`` selected, and the selected SCORES (the
    bias enters the selection only) renormalised and scaled:
    ``(weights, idx)``, both (T, topk).  The gradient reaches the
    router through the weights only."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(scores + expert_bias, topk)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * route_scale, idx


def softmax_top_k_routing(x, router_w, topk, *, route_scale=1.0):
    """Logits over ALL experts in float32, the ``topk`` largest
    selected, and the softmax over the SELECTED logits (which is the
    softmax over all of them with the selected renormalised), scaled:
    ``(weights, idx, probs)``, the first two (T, topk), ``probs``
    (T, experts) the softmax over all, which the balance loss reads.
    The gradient reaches the router through the weights and ``probs``."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    picked, idx = lax.top_k(logits, topk)
    weights = jax.nn.softmax(picked, axis=-1)
    return weights * route_scale, idx, jax.nn.softmax(logits, axis=-1)


#: the routers :func:`route` knows, by the ``score_func`` of published
#: configurations
SCORE_FUNCS = ("sigmoid", "softmax")


def route(x, router_w, topk, *, score_func="sigmoid", expert_bias=None,
          route_scale=1.0):
    """The routing of one layer as a step of its own, from whatever
    tensor the model routes on (the feed-forward's input, or the
    layer's input before attention): every token's ``topk`` of the
    ``router_w.shape[-1]`` experts and their weights, which
    :func:`routed_experts_apply` takes.

    ``score_func`` ``"sigmoid"``: :func:`score_top_k_routing` (with
    ``expert_bias``, zero without one); ``"softmax"``:
    :func:`softmax_top_k_routing`.  Returns ``(weights (T, topk)
    float32, idx (T, topk), tokens_per_expert int32 (experts,),
    mean_probs)``: ``tokens_per_expert`` is what
    :func:`updated_expert_bias` and :func:`load_balance_loss` read,
    ``mean_probs`` float32 (experts,) the mean over the tokens of the
    softmax over all experts (``None`` from the sigmoid router, which
    is balanced by its bias)."""
    num_experts = router_w.shape[-1]
    with jax.named_scope("route"):
        if score_func == "sigmoid":
            if expert_bias is None:
                expert_bias = jnp.zeros((num_experts,), jnp.float32)
            weights, idx = score_top_k_routing(
                x, router_w, expert_bias, topk, route_scale=route_scale)
            mean_probs = None
        elif score_func == "softmax":
            if expert_bias is not None:
                raise ValueError("the softmax router takes no expert_bias")
            weights, idx, probs = softmax_top_k_routing(
                x, router_w, topk, route_scale=route_scale)
            mean_probs = jnp.mean(probs, axis=0)
        else:
            raise ValueError(f"score_func must be one of {SCORE_FUNCS}, "
                             f"got {score_func!r}")
        tokens_per_expert = jnp.sum(
            idx[:, :, None] == jnp.arange(num_experts), axis=(0, 1),
            dtype=jnp.int32)
    return weights, idx, tokens_per_expert, mean_probs


def load_balance_loss(tokens_per_expert, mean_probs):
    """The auxiliary loss that balances a softmax router
    (arXiv:2101.03961 eq. 4-6): ``E * sum_e f_e P_e`` with ``f_e`` the
    share of the assignments that fell on expert e (counted, so no
    gradient passes) and ``P_e`` = ``mean_probs``; 1.0 exactly under a
    balanced router, up to ``E`` under one that sends everything to one
    expert.  Both vectors are over the tokens THIS caller routed: a
    data- or expert-parallel group that wants them over all its tokens
    reduces them first (docs/parallelism.md)."""
    with jax.named_scope("aux_loss"):
        load = lax.stop_gradient(tokens_per_expert.astype(jnp.float32))
        share = load / jnp.maximum(jnp.sum(load), 1.0)
        return mean_probs.shape[-1] * jnp.sum(share * mean_probs)


def updated_expert_bias(expert_bias, tokens_per_expert, coeff):
    """``expert_bias`` after a step in which the experts got
    ``tokens_per_expert`` (E,): up by ``coeff`` for an expert that got
    fewer than the mean, down for one that got more (the balance
    without an auxiliary loss of arXiv:2412.19437, section 2.1.2).  A
    rule of the training loop, outside the gradient."""
    load = tokens_per_expert.astype(jnp.float32)
    return expert_bias + coeff * jnp.sign(jnp.mean(load) - load)


#: rows of the grouped products' buffer are whole tiles of this many
_ROW_TILE = 512


def held_buffer_rows(n_assignments, held, num_experts):
    """Rows of the buffer the held experts' assignments pass through:
    5/4 of what a balanced router sends the held experts, in whole
    tiles, and at most every assignment there is.  A step whose held
    assignments fit passes once; one with more passes again for each
    further buffer's worth, so none is dropped."""
    balanced = -(-n_assignments * held // num_experts)
    rows = -(-(balanced * 5 // 4) // _ROW_TILE) * _ROW_TILE
    return min(rows, n_assignments)


#: a gathered table of more bytes than this is gathered in column pieces.
#: A v5e's compiler keeps a gather's table in the on-chip memory (``S(1)``
#: in the compiled program) while it fits, and the gather then runs at
#: 6 ns a row; a larger table stays in HBM and the gather takes 46 ns a
#: row.  bf16 (20,480, 2,560) = 105 MB fits, (30,720, 2,048) = 126 MB
#: does not (alone, the line is between 117 and 120 MB).
_TABLE_BYTES = 100 * 2 ** 20
#: ... of whole lane tiles
_LANES = 128
#: the sublanes of a tile: a slot table of K columns cuts one unless
#: K is a multiple
_SUBLANES = 8


def _column_pieces(n_rows, width, itemsize):
    """``[(first, last), ...]``: the columns of an ``(n_rows, width)``
    table in as few pieces of whole lane tiles as bring each under
    ``_TABLE_BYTES``; one piece for a table that is."""
    lanes = -(-width // _LANES)
    fit = max(_TABLE_BYTES // (n_rows * _LANES * itemsize), 1)
    pieces = -(-lanes // fit)           # lane tiles that fit, a piece
    step = -(-lanes // pieces) * _LANES     # ... spread evenly
    return [(first, min(first + step, width))
            for first in range(0, width, step)]


def _sum_by_owner(rows, slot, valid):
    """(N, M) from ``rows`` (R, M): owner n's sum over its K slots of
    the rows ``slot[n, k]``, where ``valid``; float32.

    In the form the chip runs fast at any shape (PERF.md, PR 35): the
    slots lead the gathered ``(K, N, M)`` where K columns would cut a
    tile (a ``(N, 6, M)`` is laid out again, 1.8 ms for 503 MB), and a
    table over ``_TABLE_BYTES`` is gathered and summed by column
    pieces.  For K a multiple of 8 and a table under the budget nothing
    is added to the one gather and the one sum."""
    n_slots = slot.shape[1]
    axis = 0 if n_slots > 1 and n_slots % _SUBLANES else 1
    index = jnp.where(valid, slot, 0)
    if axis == 0:
        index, valid = index.T, valid.T

    def piece(rows):
        picked = rows[index]
        return jnp.sum(jnp.where(valid[..., None], picked, 0), axis=axis,
                       dtype=jnp.float32)

    pieces = _column_pieces(*rows.shape, rows.dtype.itemsize)
    if len(pieces) == 1:
        return piece(rows)
    return jnp.concatenate([piece(rows[:, first:last])
                            for first, last in pieces], axis=1)


# Rows move between the owners' order (tokens) and the buffer's order
# (by expert) as GATHERS in both directions and in both passes: each
# buffer row knows its owner (``owner``), each owner knows its rows
# (``slot``, the inverse), so the transpose of a gather is the other
# gather and never the scatter-add autodiff would write (on a v5e a
# scatter-add of 20,480 rows of 2,048 takes 2.8 ms, the gather of those
# rows 0.13 ms, the gather over all 131,072 slots 0.83 ms and the
# masked sum over the slots 0.85-1.0).  Two rules keep the gather over
# the slots that fast, and ``_sum_by_owner`` follows both from the
# shapes it is given: the table it reads fits the on-chip memory, and
# the slot table does not cut a tile.

@jax.custom_vjp
def _to_rows(x, owner, owned, slot, valid):
    """Buffer rows from their owners: ``x[owner]``, zero where no group
    owns the row."""
    return jnp.where(owned, x[owner], 0)


def _to_rows_fwd(x, owner, owned, slot, valid):
    return _to_rows(x, owner, owned, slot, valid), (slot, valid)


def _to_rows_bwd(res, ct):
    slot, valid = res
    return _sum_by_owner(ct, slot, valid).astype(ct.dtype), None, None, \
        None, None


_to_rows.defvjp(_to_rows_fwd, _to_rows_bwd)


@jax.custom_vjp
def _from_rows(rows, owner, owned, slot, valid):
    """Each owner's sum of its buffer rows (float32)."""
    return _sum_by_owner(rows, slot, valid)


def _from_rows_fwd(rows, owner, owned, slot, valid):
    return _sum_by_owner(rows, slot, valid), (owner, owned, rows[:0])


def _from_rows_bwd(res, ct):
    owner, owned, like = res
    return jnp.where(owned, ct[owner], 0).astype(like.dtype), None, None, \
        None, None


_from_rows.defvjp(_from_rows_fwd, _from_rows_bwd)


#: the gate's activation of a gated expert: SwiGLU's and ReGLU's
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


#: the names (``jax.ad_checkpoint.checkpoint_name``) of what the first
#: pass of the held experts hands its backward pass: the outputs of the
#: gate's product (before the activation) and of the up product.  A
#: remat policy that keeps the dense products keeps these
#: (``models/transformer._with_remat``): a grouped product is no
#: ``dot_general``, and under ``dots`` alone the replay would run it again
KEPT_PRODUCTS = ("moe_gate_out", "moe_up_out")
#: ... and of the routed layer's output in the model's dtype, named by
#: the model (``models/transformer.RoutedExperts``).  The rule's forward
#: is one loop under ``vmap``, so a replay that needs the output (a norm
#: after the layer) would run all three products and the way back to
#: the tokens again; kept, it runs none.  Nothing is kept where the
#: backward pass does not read it
KEPT_OUTPUT = "moe_out"


def _pass_tables(rows_held, topk, start, order, slot, sizes):
    """Who owns what in the pass over the assignments ``start`` ..
    ``start + rows_held`` of ``order`` (held ones first, by expert;
    ``slot`` is its inverse): ``(rows`` the assignment of each buffer
    row, ``groups`` each expert's rows, ``owned`` (rows_held, 1) whether
    some group owns the row, ``token`` the row's owner, ``local`` the
    slots in this buffer, ``valid`` where one is an owned row, and how
    many rows are owned)``."""
    rows = lax.dynamic_slice(order, (start,), (rows_held,))
    ends = jnp.clip(jnp.cumsum(sizes) - start, 0, rows_held)
    groups = jnp.diff(ends, prepend=0)
    owned = (jnp.arange(rows_held) < ends[-1])[:, None]
    local = slot - start
    valid = (local >= 0) & (local < ends[-1])
    return rows, groups, owned, rows // topk, local, valid, ends[-1]


def _row_weights(weights, rows, owned, local, valid):
    """(rows_held, 1): each buffer row's routing weight."""
    return _to_rows(weights.reshape(-1, 1), rows, owned,
                    local.reshape(-1, 1), valid.reshape(-1, 1))


def _pass_of_experts(rows_held, topk, activation, start, y, x, order, slot,
                     sizes, weights, wi_gate, wi_up, wo):
    """``y`` plus the held experts' part for the assignments ``start``
    .. ``start + rows_held`` of ``order``: the gated unit (``activation``
    on the gate) as grouped products over the ragged groups, weighted
    and summed back by token; how many rows some group owned; and the
    first two products ``(rows_held, F)``, the gate's before its
    activation and the up product."""
    with jax.named_scope("dispatch"):
        rows, groups, owned, token, local, valid, n_owned = _pass_tables(
            rows_held, topk, start, order, slot, sizes)
        xs = _to_rows(x, token, owned, local, valid)
    with jax.named_scope("experts"):
        gate = lax.ragged_dot(xs, wi_gate, groups)
        up = lax.ragged_dot(xs, wi_up, groups)
        out = lax.ragged_dot(ACTIVATIONS[activation](gate) * up, wo, groups)
    with jax.named_scope("combine"):
        w = _row_weights(weights, rows, owned, local, valid)
        # a row no group owns holds whatever the product left there
        out = (jnp.where(owned, out, 0) * w).astype(x.dtype)
        return y + _from_rows(out, token, owned, local, valid), n_owned, \
            (gate, up)


def _product_gradients(lhs, rhs, groups, ct):
    """``ct``'s gradients to the two operands of ``ragged_dot(lhs, rhs,
    groups)``: two grouped products, and the product itself does not
    run."""
    return (jax.linear_transpose(
                lambda lhs: lax.ragged_dot(lhs, rhs, groups), lhs)(ct)[0],
            jax.linear_transpose(
                lambda rhs: lax.ragged_dot(lhs, rhs, groups), rhs)(ct)[0])


def _first_pass_gradients(rows_held, topk, activation, gate, up, x, order,
                          slot, sizes, weights, wi_gate, wi_up, wo, ct):
    """The gradients of pass 0 to ``(x, weights, wi_gate, wi_up, wo)``
    from the two products it kept, ``ct`` the cotangent of ``y``: the
    gated unit is element-wise over them, and the grouped products that
    run are the six gradients alone.  The down product's output is not
    formed again: it enters the routing weights' gradient only, ``dw =
    <ct_row, w-less out_row>``, and with ``g = ct_rows Wo^T`` that is
    ``<g, hidden>`` while the gated unit's cotangent is ``w g``: the
    scalar ``w`` moved across a linear map."""
    with jax.named_scope("dispatch"):
        rows, groups, owned, token, local, valid, _ = _pass_tables(
            rows_held, topk, 0, order, slot, sizes)
        xs = _to_rows(x, token, owned, local, valid)
    with jax.named_scope("combine"):
        w = _row_weights(weights, rows, owned, local, valid)
        ct_rows = _from_rows_bwd((token, owned, gate[:0]), ct)[0]
    with jax.named_scope("experts"):
        hidden, unit_vjp = jax.vjp(
            lambda gate, up: ACTIVATIONS[activation](gate) * up, gate, up)
        g, d_wo = _product_gradients((hidden * w).astype(hidden.dtype), wo,
                                     groups, ct_rows)
        d_gate, d_up = unit_vjp((g * w).astype(hidden.dtype))
        d_xs_gate, d_wi_gate = _product_gradients(xs, wi_gate, groups, d_gate)
        d_xs_up, d_wi_up = _product_gradients(xs, wi_up, groups, d_up)
    with jax.named_scope("combine"):
        # a row no group owns holds whatever the products left there
        d_w = jnp.sum(jnp.where(owned, g.astype(jnp.float32)
                                * hidden.astype(jnp.float32), 0),
                      axis=1, keepdims=True).astype(weights.dtype)
        d_weights = _to_rows_bwd(
            (local.reshape(-1, 1), valid.reshape(-1, 1)), d_w)[0]
    with jax.named_scope("dispatch"):
        d_x = _to_rows_bwd((local, valid), d_xs_gate + d_xs_up)[0]
    return d_x, d_weights.reshape(weights.shape), d_wi_gate, d_wi_up, d_wo


def _held_passes(n_held, rows_held):
    """The passes that ``n_held`` held assignments take through a buffer
    of ``rows_held`` rows; the first always runs."""
    return jnp.maximum(-(-n_held // rows_held), 1)


@lru_cache(maxsize=None)
def _held_experts(rows_held, topk, activation):
    """``(x, order, slot, sizes, weights, wi_gate, wi_up, wo) -> (y
    (T, M) float32, rows computed)``: as many passes through a buffer of
    ``rows_held`` rows as the held assignments need, one where they
    fit.

    The first pass always runs, so it stands outside the loop over the
    others: its gate and up products are residuals of the rule (named
    ``KEPT_PRODUCTS`` where the rule's forward hands them out, so that a
    remat policy can keep them) and its gradients are taken from them
    (``_first_pass_gradients``): six grouped products where recomputing
    the pass takes nine.  The number of FURTHER passes is
    known only on the device, so both directions loop over them on their
    own (a loop of a data-dependent length has no transpose): the
    backward pass recomputes each of those and takes its gradients
    (``jax.vjp``), summing them in the cotangents' own dtype.  All of it
    loops under ``vmap`` too (the one-device compiled step maps the loss
    over its rank axis, and a grouped product has no batching rule for
    that)."""
    one_pass = partial(_pass_of_experts, rows_held, topk, activation)

    def passes(sizes):
        return _held_passes(jnp.sum(sizes), rows_held)

    @jax.custom_batching.sequential_vmap
    def forward(x, order, slot, sizes, weights, wi_gate, wi_up, wo):
        args = (x, order, slot, sizes, weights, wi_gate, wi_up, wo)

        def one(i, carry):
            y, computed = carry
            y, owned, products = one_pass(i * rows_held, y, *args)
            return (y, computed + owned), products

        first, kept = one(0, (jnp.zeros(x.shape, jnp.float32), jnp.int32(0)))
        return lax.fori_loop(1, passes(sizes),
                             lambda i, carry: one(i, carry)[0], first), kept

    @jax.custom_batching.sequential_vmap
    def backward(gate, up, x, order, slot, sizes, weights, wi_gate, wi_up,
                 wo, ct):
        def gradients(start):
            _, vjp = jax.vjp(
                lambda x, *rest: one_pass(start, jnp.zeros_like(ct), x,
                                          order, slot, sizes, *rest)[0],
                x, weights, wi_gate, wi_up, wo)
            return vjp(ct)

        return lax.fori_loop(
            1, passes(sizes),
            lambda i, total: jax.tree.map(
                jnp.add, total, gradients(i * rows_held)),
            _first_pass_gradients(rows_held, topk, activation, gate, up, x,
                                  order, slot, sizes, weights, wi_gate,
                                  wi_up, wo, ct))

    def fwd(*args):
        out, kept = forward(*args)
        with jax.named_scope("experts"):    # whose cost the keeping is
            kept = tuple(map(checkpoint_name, kept, KEPT_PRODUCTS))
        return out, (kept, args)

    @jax.custom_vjp
    def held_experts(*args):
        return fwd(*args)[0]

    def bwd(residuals, cts):
        kept, args = residuals
        dx, dweights, dgate, dup, dwo = backward(*kept, *args, cts[0])
        return dx, None, None, None, dweights, dgate, dup, dwo

    held_experts.defvjp(fwd, bwd)
    return held_experts


def routed_experts_apply(x, weights, idx, wi_gate, wi_up, wo, *, num_experts,
                         first_expert=0, activation="silu"):
    """The routed experts of one layer as ONE member of an
    expert-parallel group computes them: it is told which experts it
    holds (``wi_gate`` / ``wi_up`` (H, M, F) and ``wo`` (H, F, M) are
    experts ``first_expert`` .. ``first_expert + H`` of the
    ``num_experts`` the router scores), TAKES the routing of every
    token over all of them (``weights``, ``idx`` (T, topk): the step
    :func:`route` makes, from ``x`` or from an earlier tensor), and
    returns the part of ``sum_j w_j FF_{idx_j}(x)`` that its own experts
    give, ``FF`` the gated unit ``(act(x Wg) * x Wu) Wo`` with
    ``activation`` ``"silu"`` (SwiGLU) or ``"relu"`` (ReGLU).
    Assignments to absent experts contribute nothing; nothing stands in
    for the members that hold them or for the exchange with them.

    No assignment to a held expert is dropped, whatever the routing:
    the assignments are sorted by expert, held ones first, and the
    products run over ragged groups (``lax.ragged_dot``) through a
    buffer of ``held_buffer_rows`` rows, as many times as it takes
    (once under a router near balance).  The first pass keeps its gate
    and up products for the backward pass (``KEPT_PRODUCTS``), which
    then runs that pass's six gradient products and none of its forward
    again; every further pass is recomputed there (``_held_experts``).

    ``x`` (T, M) in the products' dtype.  Returns ``(y (T, M) float32,
    counts)``; ``counts`` is int32 (5,): the assignments (T * topk),
    those that fell on held experts, those of them that were not
    computed (0, or the layer is not dropless), the passes through the
    buffer, and those of them beyond the first (0, or the backward pass
    ran that many passes' forward again)."""
    T, topk = idx.shape
    held = wi_gate.shape[0]
    n = T * topk
    with jax.named_scope("dispatch"):
        local = idx.reshape(n) - first_expert
        key = jnp.where((local >= 0) & (local < held), local, held)
        rows_held = held_buffer_rows(n, held, num_experts)
        # the assignments by expert, held ones first, with room for the
        # last pass to read a whole buffer ...
        order = jnp.pad(jnp.argsort(key, stable=True).astype(jnp.int32),
                        (0, -n % rows_held))
        # ... and its inverse for the held ones, without a second sort:
        # a group's offset plus how many of the group came earlier
        mine = key[:, None] == jnp.arange(held)[None]
        sizes = jnp.sum(mine, axis=0, dtype=jnp.int32)
        earlier = jnp.cumsum(mine, axis=0, dtype=jnp.int32) - 1 \
            + (jnp.cumsum(sizes) - sizes)[None]
        slot = jnp.where(key < held, jnp.sum(
            jnp.where(mine, earlier, 0), axis=1), n).reshape(T, topk)
    y, computed = _held_experts(rows_held, topk, activation)(
        x, order, slot, sizes, weights, wi_gate, wi_up, wo)
    n_held = jnp.sum(sizes)
    passes = _held_passes(n_held, rows_held)
    return y, jnp.stack([jnp.int32(n), n_held, n_held - computed, passes,
                         passes - 1])


# ---------------------------------------------------------------------------
# the in-graph quantized exchange

def _a2a_codec(x, wire):
    """Block-scaled encode of ``x`` (R, n) f32 per destination slot
    -> (payload, scales); the in-graph twin of ops/quantize.py's
    numpy codec (BLOCK=256, bf16 scales) and of the fused codec in
    ops/compiled.CompiledAlltoall."""
    from ..ops import quantize as qz

    R, n = x.shape
    B = qz.BLOCK
    npad = -(-n // B) * B
    qmax = 7 if wire == "int4" else 127
    xp = jnp.pad(x, ((0, 0), (0, npad - n)))
    xb = xp.reshape(R, npad // B, B)
    absmax = jnp.max(jnp.abs(xb), axis=-1)
    scales = (absmax / jnp.float32(qmax)).astype(jnp.bfloat16) \
        .astype(jnp.float32)
    safe = jnp.where(scales > 0, scales, jnp.float32(1.0))
    q = jnp.clip(jnp.round(xb / safe[..., None]), -qmax, qmax) \
        .astype(jnp.int8).reshape(R, npad)
    if wire == "int4":
        b = (q.astype(jnp.int16) + 8).astype(jnp.uint8)
        q = b[:, 0::2] | (b[:, 1::2] << 4)
    return q, scales


def _a2a_decode(q, scales, n, wire):
    from ..ops import quantize as qz

    B = qz.BLOCK
    R = q.shape[0]
    if wire == "int4":
        lo = (q & 0xF).astype(jnp.int8) - 8
        hi = (q >> 4).astype(jnp.int8) - 8
        q = jnp.stack([lo, hi], axis=-1).reshape(R, -1)
    xb = q.reshape(R, -1, B).astype(jnp.float32) * scales[..., None]
    return xb.reshape(R, -1)[:, :n]


def _qa2a_exchange(x, axis_name, wire):
    a2a = partial(lax.all_to_all, axis_name=axis_name, split_axis=0,
                  concat_axis=0, tiled=True)
    if wire in ("int8", "int4"):
        xf = x.astype(jnp.float32)
        q, s = _a2a_codec(xf, wire)
        return _a2a_decode(a2a(q), a2a(s), x.shape[1], wire) \
            .astype(x.dtype)
    if wire in ("fp16", "bf16"):
        wdt = jnp.float16 if wire == "fp16" else jnp.bfloat16
        return a2a(x.astype(wdt)).astype(x.dtype)
    return a2a(x)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def quantized_all_to_all(x, axis_name, wire=None):
    """``lax.all_to_all`` with the block-scaled wire codec fused in:
    int8 codes / packed int4 nibbles plus bf16 block scales are what
    actually cross ``axis_name`` — the in-graph (shard_map) twin of
    ``CompiledAlltoall``, for MoE layers compiled over an ``ep``
    mesh axis.

    ``x`` is (R, n) per participant: slot j goes to rank j, slot j of
    the result came from rank j.  Differentiable: the backward pass
    is the same exchange of the cotangent (the alltoall permutation
    is its own transpose) with the codec STRAIGHT-THROUGH — the
    quantization error is treated as identity in the VJP, the same
    estimator the reducers' error feedback assumes."""
    return _qa2a_exchange(x, axis_name, wire)


def _qa2a_fwd(x, axis_name, wire):
    return _qa2a_exchange(x, axis_name, wire), None


def _qa2a_bwd(axis_name, wire, _res, g):
    return (_qa2a_exchange(g, axis_name, wire),)


quantized_all_to_all.defvjp(_qa2a_fwd, _qa2a_bwd)


def dense_flop_matched_ff(d_ff_expert, topk):
    """Hidden width of the dense FFN whose per-token FLOPs match a
    top-k MoE with per-expert hidden ``d_ff_expert``: each token runs
    ``topk`` experts, so the matched dense width is their sum.  The
    lm_bench loss-parity gate trains this baseline against the MoE
    config on identical data (docs/parallelism.md)."""
    return int(d_ff_expert) * int(topk)
