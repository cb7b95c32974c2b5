"""Adapter: Ouro's looped language model (one stack of layers run
``total_ut_steps`` times over the same weights, the final norm and an
exit gate after every pass, the expected loss over the exits) through
the program's normal training path, as ``lm_train`` drives Mistral:
``hvd.init``, ``TransformerLM`` with the Pallas flash-attention kernel,
the fused chunked cross-entropy (``make_fused_lm_loss``, which gives a
looped model its objective), ``hvd.make_compiled_train_step`` with
``optax.adamw``."""

import functools

from chipbench import looped_flops
from chipbench.adapters.hvd_runtime import launch, replicas_agree  # noqa: F401
from chipbench.adapters.lm_train import (  # noqa: F401
    first_gradient, init_state, optimizer)


def program_config(config, workload):
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        max_seq_len=workload["seq_len"], rope_theta=config["rope_theta"],
        rms_norm_eps=config["rms_norm_eps"],
        tie_word_embeddings=config["tie_word_embeddings"],
        sandwich_norm=True, layer_types=tuple(config["layer_types"]),
        sliding_window=config["sliding_window"],
        total_ut_steps=config["total_ut_steps"],
        exit_entropy_coeff=config["exit_entropy_coeff"],
        dtype=jnp.bfloat16, remat=True,
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


def make_step(config, workload, rehearse):
    import horovod_tpu as hvd

    return hvd.make_compiled_train_step(
        loss_fn(config, workload, rehearse), optimizer(workload))


def flops_per_sample(config, workload):
    """Model FLOPs of forward and backward for one token: every pass's
    layers and every pass's exit."""
    return looped_flops.train_flops_per_token(config, workload["seq_len"])


def attention_flops_per_sample(config, workload):
    return looped_flops.attention_train_flops_per_token(
        config, workload["seq_len"])
