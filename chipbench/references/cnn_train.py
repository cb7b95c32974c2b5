"""Plain float32 reference of the ``cnn_train`` adapter: a bottleneck
ResNet (v1.5: the stride sits in the 3x3 convolution) with BatchNorm on
the batch's own statistics, trained with SGD and momentum on softmax
cross-entropy.

Straightforward ``jax.numpy`` and ``lax.conv_general_dilated``: no
bfloat16, no kernel, nothing imported from the program.  It makes its
own weights from the key and follows the first steps of training as
the mix's ranks take them: each rank's rows are a batch of their own
(BatchNorm couples the rows of one rank and no others: Horovod trains
without a synchronised BatchNorm), the loss and the gradient are the
means over the ranks.  One bottleneck block is recomputed at a time so
that it fits, the like blocks of a stage under one ``lax.scan``, one
rank's rows after another's.  The running statistics, which no training
step reads, are not followed.
"""

import functools
import itertools
import math

import jax
import jax.numpy as jnp

from chipbench import weights
from chipbench.references import precision
from chipbench.weights import Leaf

def first_loss(config):
    """Seeded weights know nothing: the first loss is near ln(classes)
    (above it, by what the seeded classifier's logits spread)."""
    return math.log(config["num_classes"])


def _blocks(config):
    """(name, c_in, mid, stride, projected) of every bottleneck."""
    c_in, out = config["num_filters"], []
    for stage, count in enumerate(config["stage_sizes"]):
        mid = config["num_filters"] * 2 ** stage
        for block in range(count):
            stride = 2 if stage > 0 and block == 0 else 1
            out.append((f"BottleneckBlock_{len(out)}", c_in, mid, stride,
                        c_in != 4 * mid or stride != 1))
            c_in = 4 * mid
    return out, c_in


def _trees(config):
    """(parameters, batch statistics) as ``Leaf`` trees, named as the
    program's flax modules name them."""
    def conv(kh, kw, c_in, c_out):
        return {"kernel": Leaf((kh, kw, c_in, c_out), "normal",
                               1.0 / math.sqrt(kh * kw * c_in))}

    def norm(c):
        return {"scale": Leaf((c,), "ones"), "bias": Leaf((c,), "zeros")}

    def stats(c):
        return {"mean": Leaf((c,), "zeros"), "var": Leaf((c,), "ones")}

    width = config["num_filters"]
    params = {"conv_init": conv(7, 7, config["num_channels"], width),
              "bn_init": norm(width)}
    batch_stats = {"bn_init": stats(width)}
    blocks, c_out = _blocks(config)
    for name, c_in, mid, _, projected in blocks:
        params[name] = {
            "Conv_0": conv(1, 1, c_in, mid), "BatchNorm_0": norm(mid),
            "Conv_1": conv(3, 3, mid, mid), "BatchNorm_1": norm(mid),
            "Conv_2": conv(1, 1, mid, 4 * mid), "BatchNorm_2": norm(4 * mid)}
        batch_stats[name] = {"BatchNorm_0": stats(mid),
                             "BatchNorm_1": stats(mid),
                             "BatchNorm_2": stats(4 * mid)}
        if projected:
            params[name]["conv_proj"] = conv(1, 1, c_in, 4 * mid)
            params[name]["norm_proj"] = norm(4 * mid)
            batch_stats[name]["norm_proj"] = stats(4 * mid)
    params["head"] = {
        "kernel": Leaf((c_out, config["num_classes"]), "normal",
                       1.0 / math.sqrt(c_out)),
        "bias": Leaf((config["num_classes"],), "zeros")}
    return params, batch_stats


def param_spec(config):
    return _trees(config)[0]


def aux_spec(config):
    return _trees(config)[1]


def batch_loss(config, products, params, images, labels):
    einsum, conv = products
    eps = config["bn_epsilon"]

    def norm(x, p):
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(x * x, axis=(0, 1, 2)) - mean * mean
        return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]

    @functools.partial(jax.checkpoint, static_argnums=2)
    def bottleneck(x, p, stride):
        y = conv(x, p["Conv_0"]["kernel"], (1, 1), "SAME")
        y = jax.nn.relu(norm(y, p["BatchNorm_0"]))
        y = conv(y, p["Conv_1"]["kernel"], (stride, stride), "SAME")
        y = jax.nn.relu(norm(y, p["BatchNorm_1"]))
        y = conv(y, p["Conv_2"]["kernel"], (1, 1), "SAME")
        y = norm(y, p["BatchNorm_2"])
        if "conv_proj" in p:
            x = norm(conv(x, p["conv_proj"]["kernel"], (stride, stride),
                          "SAME"), p["norm_proj"])
        return jax.nn.relu(x + y)

    @jax.checkpoint
    def stem(x, kernel, p):
        x = conv(x, kernel, (2, 2), ((3, 3), (3, 3)))
        x = jax.nn.relu(norm(x, p))
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            ((0, 0), (1, 1), (1, 1), (0, 0)))

    x = stem(images.astype(jnp.float32), params["conv_init"]["kernel"],
             params["bn_init"])
    # a stage's blocks after its first are alike: one body, scanned over
    # their stacked parameters, so the program holds 8 bodies and not 16
    for (_, _, stride, _), alike in itertools.groupby(
            _blocks(config)[0], key=lambda block: block[1:]):
        names = [block[0] for block in alike]
        if len(names) == 1:
            x = bottleneck(x, params[names[0]], stride)
            continue
        stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                               *(params[name] for name in names))
        x, _ = jax.lax.scan(
            lambda x, p, stride=stride: (bottleneck(x, p, stride), None),
            x, stacked)
    x = jnp.mean(x, axis=(1, 2))
    logits = einsum("nc,ck->nk", x, params["head"]["kernel"]) \
        + params["head"]["bias"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def loss_and_grad(config, products, ranks):
    """``(params, batch) -> (loss, gradient)`` of one step as ``ranks``
    ranks compute it: the batch's rows in ``ranks`` groups in order, a
    rank's rows each; ``batch_loss``, and so BatchNorm's mean and
    variance, over each group alone; the loss and the gradient the
    means over the groups, which is what the step's all-reduce makes of
    them.  One group at a time, so that four ranks' rows fit where one
    rank's do.  One rank is ``batch_loss`` itself on the whole batch."""
    grad = jax.value_and_grad(
        lambda p, b: batch_loss(config, products, p, *b))
    if ranks == 1:
        return grad

    def mean_over_ranks(params, batch):
        groups = jax.tree.map(
            lambda a: a.reshape((ranks, -1) + a.shape[1:]), batch)
        losses, grads = jax.lax.map(lambda b: grad(params, b), groups)
        return jnp.mean(losses), jax.tree.map(
            lambda g: jnp.mean(g, axis=0), grads)

    return mean_over_ranks


def step_program(config, workload, mode="float32"):
    """The jitted ``(params, trace, batch) -> (params, trace, loss,
    norms of the gradient's leaves)``: one step of SGD with momentum on
    the mean gradient of the mix's ranks."""
    opt = workload["optimizer"]
    if opt["name"] != "sgd":
        raise NotImplementedError(f"optimizer {opt['name']!r}")
    grad = loss_and_grad(config, precision.products(mode),
                         workload["ranks"])

    @jax.jit
    def one_step(params, trace, batch):
        loss, grads = grad(params, batch)
        trace = jax.tree.map(lambda t, g: g + opt["momentum"] * t,
                             trace, grads)
        params = jax.tree.map(lambda p, t: p - opt["learning_rate"] * t,
                              params, trace)
        return params, trace, loss, weights.leaf_norms(grads)

    return one_step


def follow(config, workload, key, batch, steps, mode="float32"):
    """The first ``steps`` steps of training on the fixed ``batch``
    (images, labels: ``workload["ranks"]`` ranks' rows, a rank's after
    another's) from the weights of ``key``, on the host: ``{"losses":
    [steps], "grad_norms": {leaf: norm of the first gradient},
    "delta_norms": {leaf: norm of the parameters' change}}``.
    """
    one_step = step_program(config, workload, mode)
    spec = param_spec(config)
    params = jax.jit(lambda k: weights.make(k, spec))(key)
    trace = jax.tree.map(jnp.zeros_like, params)
    found = {"losses": []}
    for i in range(steps):
        params, trace, loss, norms = one_step(params, trace, batch)
        found["losses"].append(float(loss))
        if i == 0:
            found["grad_norms"] = jax.device_get(norms)
    found["delta_norms"] = jax.device_get(jax.jit(
        lambda p, k: weights.leaf_norms(jax.tree.map(
            lambda a, b: a - b, p, weights.make(k, spec))))(params, key))
    return found
