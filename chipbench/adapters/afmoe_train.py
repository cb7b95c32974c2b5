"""Adapter: Trinity-Mini's architecture (``afmoe``: sliding and full
attention layers in one model, gated QK-normed attention, leading dense
layers, then a sigmoid router over all the experts with a dropless
grouped product over the ones held here, an untied head) through the
program's normal training path, as ``lm_train`` drives Mistral:
``hvd.init``, ``TransformerLM`` with the Pallas flash-attention kernel,
the fused chunked cross-entropy, ``hvd.make_compiled_train_step`` with
``optax.adamw``.  The configuration is one chip's share of an
expert-parallel deployment: its ``num_experts`` is what this chip holds,
``published.num_experts`` what the router scores."""

import functools

from chipbench import afmoe_flops
from chipbench.adapters.hvd_runtime import launch, replicas_agree  # noqa: F401
from chipbench.adapters.lm_train import first_gradient, optimizer  # noqa: F401


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
        qk_norm=True, attention_gate=True, sandwich_norm=True,
        rope_on_full_attention=False, mup_enabled=config["mup_enabled"],
        layer_types=tuple(config["layer_types"]),
        sliding_window=config["sliding_window"],
        num_dense_layers=config["num_dense_layers"],
        num_experts=afmoe_flops.routed_width(config),
        num_experts_held=config["num_experts"],
        first_expert_held=config.get("deployment", {}).get(
            "first_expert_held", 0),
        expert_top_k=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_shared_experts=config["num_shared_experts"],
        score_func=config["score_func"], route_norm=config["route_norm"],
        route_scale=config["route_scale"],
        load_balance_coeff=config["load_balance_coeff"],
        dtype=jnp.bfloat16, remat=True,
        remat_policy=config["remat_policy"])


def param_shapes(config, workload):
    """The program's own trees as shapes (nothing is run): the
    parameters, and what the training loop keeps beside them (the
    routed layers' ``router_state``: each one's expert_bias)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerLM

    tokens = jax.ShapeDtypeStruct((1, workload["seq_len"]), jnp.int32)
    shapes = dict(jax.eval_shape(
        lambda t: TransformerLM(program_config(config, workload)).init(
            jax.random.PRNGKey(0), t), tokens))
    return shapes.pop("params"), shapes


def loss_fn(config, workload, rehearse):
    from horovod_tpu.models import TransformerLM, make_fused_lm_loss
    from horovod_tpu.ops.pallas_kernels import flash_attention

    attention = functools.partial(flash_attention, interpret=True) \
        if rehearse else flash_attention
    model = TransformerLM(program_config(config, workload),
                          attention_fn=attention)
    return make_fused_lm_loss(model, n_chunks=config["cross_entropy_chunks"],
                              with_state=True)


def make_step(config, workload, rehearse):
    import horovod_tpu as hvd

    return hvd.make_compiled_train_step(
        loss_fn(config, workload, rehearse), optimizer(workload),
        has_aux=True)


def init_state(step, params, aux):
    return step.init_state(params, aux=aux)


def flops_per_sample(config, workload):
    """Model FLOPs of forward and backward for one token, of what this
    chip computes under a balanced router."""
    return afmoe_flops.train_flops_per_token(config, workload["seq_len"])


def attention_flops_per_sample(config, workload):
    return afmoe_flops.attention_train_flops_per_token(config,
                                                       workload["seq_len"])
