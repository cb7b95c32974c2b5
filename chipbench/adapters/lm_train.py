"""Adapter: a decoder-only language model through the program's normal
training path — ``hvd.init`` / ``hvd.run``, ``TransformerLM`` with the
Pallas flash-attention kernel, the fused chunked cross-entropy,
``hvd.make_compiled_train_step`` with ``optax.adamw``.  One rank on one
chip, or one rank per chip as threads of this process under ``hvd.run``.
(``model_and_loss`` follows benchmarks/lm_mfu_bench.py, which lives
outside the package and may change.)
"""

import functools

from chipbench import flops
from chipbench.adapters.hvd_runtime import launch, replicas_agree  # noqa: F401


def program_config(config, workload):
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], max_seq_len=workload["seq_len"],
        attention_window=config["sliding_window"],
        rope_theta=config["rope_theta"], dtype=jnp.bfloat16, remat=True,
        remat_policy=config["remat_policy"])


def param_shapes(config, workload):
    """The program's own parameter tree as shapes (nothing is run)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerLM

    tokens = jax.ShapeDtypeStruct((1, workload["seq_len"]), jnp.int32)
    return jax.eval_shape(
        lambda t: TransformerLM(program_config(config, workload)).init(
            jax.random.PRNGKey(0), t)["params"], tokens), None


def loss_fn(config, workload, rehearse):
    from horovod_tpu.models import TransformerLM, make_fused_lm_loss
    from horovod_tpu.ops.pallas_kernels import flash_attention

    attention = functools.partial(flash_attention, interpret=True) \
        if rehearse else flash_attention
    model = TransformerLM(program_config(config, workload),
                          attention_fn=attention)
    return make_fused_lm_loss(model, n_chunks=config["cross_entropy_chunks"])


def optimizer(workload):
    import optax

    opt = workload["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"lm_train trains with adamw, not {opt['name']!r}")
    return optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                       eps=opt["eps"], weight_decay=opt["weight_decay"])


def make_step(config, workload, rehearse):
    import horovod_tpu as hvd

    return hvd.make_compiled_train_step(
        loss_fn(config, workload, rehearse), optimizer(workload))


def init_state(step, params, aux):
    return step.init_state(params)


def first_gradient(state, workload):
    """The gradient the optimizer was handed in its first step, from
    the state after that step: AdamW's first moment is (1 - b1) g."""
    import jax

    b1 = workload["optimizer"]["b1"]
    return jax.tree.map(lambda m: m / (1 - b1), state["opt_state"][0].mu)


def flops_per_sample(config, workload):
    """Model FLOPs of forward and backward for one token."""
    return flops.lm_train_flops_per_token(config, workload["seq_len"])


def attention_flops_per_sample(config, workload):
    return flops.lm_attention_train_flops_per_token(config,
                                                    workload["seq_len"])
