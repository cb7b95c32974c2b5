"""Adapter: a bottleneck ResNet through the program's normal training
path — ``hvd.init`` / ``hvd.run``, ``horovod_tpu.models.ResNet`` (for
ResNet-50's sizes this is ``ResNet50(num_classes=1000)``),
``hvd.make_compiled_train_step`` with ``has_aux`` batch statistics and
``optax.sgd`` with momentum, as bench.py builds it.
"""

from chipbench import flops
from chipbench.adapters.hvd_runtime import launch, replicas_agree  # noqa: F401


def model(config):
    from horovod_tpu.models import ResNet

    return ResNet(stage_sizes=config["stage_sizes"],
                  num_classes=config["num_classes"],
                  num_filters=config["num_filters"])


def param_shapes(config, workload):
    import jax
    import jax.numpy as jnp

    size = config["image_size"]
    images = jax.ShapeDtypeStruct(
        (1, size, size, config["num_channels"]),
        jnp.dtype(workload["input"]["dtype"]))
    variables = jax.eval_shape(
        lambda x: model(config).init(jax.random.PRNGKey(0), x, train=False),
        images)
    return variables["params"], variables["batch_stats"]


def loss_fn(config, workload, rehearse):
    """``(params, batch_stats, batch) -> (loss, new batch_stats)``, the
    adapters' shared signature (the mix and the rehearsal change
    nothing of it)."""
    import jax
    import jax.numpy as jnp

    net = model(config)

    def loss(params, batch_stats, batch):
        images, labels = batch
        logits, mutated = net.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(
            logp, labels[:, None], axis=-1)), mutated["batch_stats"]

    return loss


def optimizer(workload):
    import optax

    opt = workload["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError(f"cnn_train trains with sgd, not {opt['name']!r}")
    return optax.sgd(opt["learning_rate"], momentum=opt["momentum"])


def make_step(config, workload, rehearse):
    import horovod_tpu as hvd

    return hvd.make_compiled_train_step(
        loss_fn(config, workload, rehearse), optimizer(workload),
        has_aux=True)


def init_state(step, params, aux):
    return step.init_state(params, aux=aux)


def first_gradient(state, workload):
    """After the first step the momentum trace is the first gradient."""
    return state["opt_state"][0].trace


def flops_per_sample(config, workload):
    """Model FLOPs of forward and backward for one image."""
    return flops.resnet_train_flops_per_image(config)
