"""Adapter: Granite-4.0-H's hybrid architecture (Mamba-2 state-space
layers with a grouped-query attention layer without position encoding
among them, a dense SwiGLU in every layer, a tied head, four muP-style
multipliers) through the program's normal training path, as ``lm_train``
drives Mistral: ``hvd.init``, ``TransformerLM`` with the Pallas
flash-attention kernel on the attention layer and ``models/mamba.py``'s
chunked scan on the others, the fused chunked cross-entropy (which
divides the logits by ``logits_scaling``), ``hvd.make_compiled_train_step``
with ``optax.adamw``."""

import functools

from chipbench import ssm_flops
from chipbench.adapters.hvd_runtime import launch, replicas_agree  # noqa: F401
from chipbench.adapters.lm_train import (  # noqa: F401
    first_gradient, init_state, optimizer)

# the published kinds of layer as the program names them; its full
# attention takes no position encoding with rope_on_full_attention off
_KINDS = {"mamba": "mamba", "attention": "full_attention"}


def program_config(config, workload):
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerConfig

    if config["position_embedding_type"] != "nope":
        raise ValueError("the attention layers take no position encoding")
    if config["num_local_experts"] or config["mamba_proj_bias"] \
            or config["attention_bias"] or not config["mamba_conv_bias"]:
        raise ValueError("no routed experts, no projection biases, a "
                         "convolution with its bias")
    heads = config["num_attention_heads"]
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"], n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // heads,
        d_ff=config["shared_intermediate_size"],
        max_seq_len=workload["seq_len"],
        rms_norm_eps=config["rms_norm_eps"],
        tie_word_embeddings=config["tie_word_embeddings"],
        rope_on_full_attention=False,
        layer_types=tuple(_KINDS[kind] for kind in config["layer_types"]),
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_n_groups=config["mamba_n_groups"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_chunk_size=config["mamba_chunk_size"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        logits_scaling=config["logits_scaling"],
        dtype=jnp.bfloat16, remat=True,
        remat_policy=config["remat_policy"])


def param_shapes(config, workload):
    """The program's own parameter tree as shapes (nothing is run); the
    training loop keeps nothing beside it."""
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
    """Model FLOPs of forward and backward for one token."""
    return ssm_flops.train_flops_per_token(config, workload["seq_len"])


def attention_flops_per_sample(config, workload):
    return ssm_flops.attention_train_flops_per_token(config,
                                                     workload["seq_len"])
