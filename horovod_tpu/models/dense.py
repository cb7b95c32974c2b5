"""The product of a layer's dense projection, with its backward written
out: the weight's gradient arrives in the weight's own layout.

jax's transpose rule for a product's right-hand side computes ``dy^T x``,
which is ``(out, in)``, and transposes the result.  The TPU compiler
folds that transpose into the result's LAYOUT; the optimizer's
arithmetic, fused behind the product, inherits the layout, and because
the state enters and leaves the step in the default one, the parameter
and both of AdamW's moments are each copied to the transposed layout on
the way in and back on the way out: six copies of a leaf's size for
every projection, every step (PERF.md section 6, PR 49).
``dense_product`` contracts ``x`` with ``dy`` over ``x``'s leading axes
instead: the result is ``x``'s contracted axes followed by ``dy``'s
feature axes, the kernel's shape in the kernel's order, and nothing
follows it.

Handed to flax as ``dot_general=`` (``nn.Dense`` and ``nn.DenseGeneral``
both take it) by every projection of a layer of a model whose layers
differ (``transformer.LayerPeriod`` chooses): ``SwiGLU``'s three,
attention's ``wq`` / ``wk`` / ``wv`` / ``wg`` / ``wo``, the mamba
mixer's ``in_proj`` / ``out_proj``.  Not by the routers and the exit
gates (float32, a few columns), the loss head (``chunked_lm_loss``, its
own rule) or the routed experts' grouped products (``parallel/moe.py``).

**Where it pays, and ``WRITTEN_BACKWARD_ROWS``.**  The product in the
kernel's own orientation is the SLOWER matmul on the chip: Granite's
``wi_gate`` / ``wi_up`` weight-gradient fusions (d2048 x 8192 over
8,192 rows) take 2.67 ms for 2.23, Trinity's (d2048 x 6144 over 16,384
rows) 3.95 for 2.73, its ``wg`` (d2048 x 32 x 128) 2.73 for 1.88.  What
it saves, the copies, costs the same whatever the rows: the penalty
grows with the rows a step's product contracts over and the saving does
not.  Measured whole (``tokens_per_s_per_chip``, parent -> every
projection through this product; my chip runs, PR 49): 4,096 rows
+2.80% (Ouro), 8,192 rows +6.16% (Granite); 16,384 rows +0.74%
(SmallThinker), -0.46% and -1.23% (Mistral at 2 x 8,192 and 4 x 4,096),
-2.99% (Trinity).  So a layer takes it up to the largest row count at
which it was seen to win, and flax's default (jax's rule) above; the
plain model's ``DecoderBlock``, which no cell runs at so few rows and
which serving applies forward-only, keeps the default.

**The forward rule calls ``lax.dot_general`` itself.**  A forward rule
that calls the ``custom_vjp`` function again shows the remat policies a
``custom_vjp_call`` where they look for a ``dot_general``:
``dots_with_no_batch_dims_saveable`` then keeps no projection's product
and every ``dots`` model replays them in its backward
(``jax.ad_checkpoint.print_saved_residuals`` shows it on a CPU;
``tests/test_dense_grad.py`` plants that form and catches it).  The
primal program is the plain product either way, so serving's
forward-only use and the StableHLO of a forward pass do not change.
The price is the one ``chunked_lm_loss`` pays: no forward-mode
differentiation through a projection (nothing in ``horovod_tpu`` takes
it).
"""

import functools

import jax
from jax import lax

#: the most rows (a rank's sequences x their length) of a step at which
#: a layer's projections take ``dense_product``: the largest count at
#: which the chip read a gain (the docstring has the readings)
WRITTEN_BACKWARD_ROWS = 8192


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def dense_product(x, w, dimension_numbers, precision=None):
    """``lax.dot_general(x, w, dimension_numbers)`` as flax's ``Dense``
    and ``DenseGeneral`` call theirs: ``x``'s trailing axes contracted
    with ``w``'s leading ones in order, no batch axes; operands in the
    compute dtype (flax has promoted both)."""
    return _product(x, w, dimension_numbers, precision)


def _product(x, w, dimension_numbers, precision):
    (x_contract, w_contract), batch = dimension_numbers
    n = len(x_contract)
    if tuple(batch) != ((), ()) or tuple(w_contract) != tuple(range(n)) \
            or tuple(x_contract) != tuple(range(x.ndim - n, x.ndim)):
        raise ValueError(
            "dense_product contracts x's trailing axes with w's leading "
            f"ones, in order and with no batch axes; got {dimension_numbers}")
    return lax.dot_general(x, w, dimension_numbers, precision=precision)


def _forward(x, w, dimension_numbers, precision):
    return _product(x, w, dimension_numbers, precision), (x, w)


def _backward(dimension_numbers, precision, kept, dy):
    x, w = kept
    n = len(dimension_numbers[0][0])
    lead = tuple(range(x.ndim - n))         # of x and of dy
    dy_feats = tuple(range(len(lead), dy.ndim))
    w_feats = tuple(range(n, w.ndim))
    # x's leading axes, then w's contracted ones: x's shape
    dx = lax.dot_general(dy, w, ((dy_feats, w_feats), ((), ())),
                         precision=precision)
    # x's contracted axes, then dy's features: the kernel's shape in the
    # kernel's order, and nothing follows it
    dw = lax.dot_general(x, dy, ((lead, lead), ((), ())),
                         precision=precision)
    return dx, dw


dense_product.defvjp(_forward, _backward)
