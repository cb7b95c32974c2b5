"""Adapter: SmallThinker's architecture (a softmax top-k router that
reads the layer's input BEFORE attention, ReGLU experts balanced by an
auxiliary loss, global layers without position encoding among windowed
rotary ones, no dense feed-forward, an untied head) through the
program's normal training path, as ``afmoe_train`` drives Trinity-Mini:
``hvd.init``, ``TransformerLM`` with the Pallas flash-attention kernel,
the fused chunked cross-entropy (which adds the balance loss),
``hvd.make_compiled_train_step`` with ``optax.adamw``.  The
configuration is one chip's share of an expert-parallel deployment: its
``moe_num_primary_experts`` is what this chip holds,
``published.moe_num_primary_experts`` what the router scores."""

import functools

from chipbench import smallthinker_flops
from chipbench.adapters.hvd_runtime import launch, replicas_agree  # noqa: F401
from chipbench.adapters.lm_train import (  # noqa: F401
    first_gradient, init_state, optimizer)

_KINDS = {0: "full_attention", 1: "sliding_attention"}


def program_config(config, workload):
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerConfig

    if config["rope_layout"] != config["sliding_window_layout"]:
        raise ValueError("the program puts rotary positions on the window "
                         "layers and on no other: the layouts differ")
    if not (config["moe_primary_router_apply_softmax"]
            and config["norm_topk_prob"]):
        raise ValueError("the router is a softmax over the selected logits")
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], max_seq_len=workload["seq_len"],
        rope_theta=config["rope_theta"],
        rms_norm_eps=config["rms_norm_eps"],
        tie_word_embeddings=config["tie_word_embeddings"],
        rope_on_full_attention=False,
        layer_types=tuple(_KINDS[w] for w in config["sliding_window_layout"]),
        sliding_window=config["sliding_window_size"],
        num_experts=config["published"]["moe_num_primary_experts"],
        num_experts_held=config["moe_num_primary_experts"],
        first_expert_held=config["deployment"]["first_expert_held"],
        expert_top_k=config["moe_num_active_primary_experts"],
        moe_intermediate_size=config["moe_ffn_hidden_size"],
        score_func="softmax", router_before_attention=True,
        expert_activation="relu",
        router_aux_loss_coef=config["router_aux_loss_coef"],
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
    """Model FLOPs of forward and backward for one token, of what this
    chip computes under a balanced router."""
    return smallthinker_flops.train_flops_per_token(config,
                                                    workload["seq_len"])


def attention_flops_per_sample(config, workload):
    return smallthinker_flops.attention_train_flops_per_token(
        config, workload["seq_len"])
