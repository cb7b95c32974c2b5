"""Adapter: Kimi-Linear's architecture (``kimi_linear``: Kimi Delta
Attention layers, a gated delta rule with a decay a key channel, three
to one with latent-attention layers without position encoding; a
leading dense layer, then Trinity-Mini's kind of router, a sigmoid over
all the experts with a bias that enters the selection only, onto a
dropless grouped product over the experts held here; an untied head)
through the program's normal training path, as ``afmoe_train`` drives
Trinity: ``hvd.init``, ``TransformerLM`` with the Pallas flash-attention
kernel on the latent layer and ``models/kda.py``'s chunked rule on the
others, the fused chunked cross-entropy,
``hvd.make_compiled_train_step`` with ``optax.adamw``.  The
configuration is one chip's share of an expert-parallel deployment: its
``num_experts`` is what this chip holds, ``published.num_experts`` what
the router scores."""

import functools

from chipbench import kimi_linear_flops
from chipbench.adapters.afmoe_train import init_state  # noqa: F401
from chipbench.adapters.hvd_runtime import launch, replicas_agree  # noqa: F401
from chipbench.adapters.lm_train import first_gradient, optimizer  # noqa: F401


def program_config(config, workload):
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerConfig

    if config["q_lora_rank"] is not None or not config["mla_use_nope"] \
            or config["num_expert_group"] != 1 or config["topk_group"] != 1 \
            or not config["moe_renormalize"] or config["moe_layer_freq"] != 1:
        raise ValueError(
            "queries projected from the input, no position encoding, one "
            "group of experts, renormalised weights, experts in every "
            "layer after the dense ones")
    heads, width, _, taps = kimi_linear_flops.kda_sizes(config)
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        # read by no layer of this model: the rotary table's width
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        max_seq_len=workload["seq_len"], rope_theta=config["rope_theta"],
        rms_norm_eps=config["rms_norm_eps"],
        tie_word_embeddings=config["tie_word_embeddings"],
        layer_types=tuple(config["layer_types"]),
        num_dense_layers=config["first_k_dense_replace"],
        kda_n_heads=heads, kda_d_head=width, kda_d_conv=taps,
        kda_chunk_size=config["kda_chunk_size"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        num_experts=kimi_linear_flops.routed_width(config),
        num_experts_held=config["num_experts"],
        first_expert_held=config.get("deployment", {}).get(
            "first_expert_held", 0),
        expert_top_k=config["num_experts_per_token"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_shared_experts=config["num_shared_experts"],
        score_func=config["moe_router_activation_func"],
        route_norm=config["moe_renormalize"],
        route_scale=config["routed_scaling_factor"],
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


def flops_per_sample(config, workload):
    """Model FLOPs of forward and backward for one token, of what this
    chip computes under a balanced router, at the published widths."""
    return kimi_linear_flops.train_flops_per_token(config,
                                                   workload["seq_len"])


def attention_flops_per_sample(config, workload):
    return kimi_linear_flops.attention_train_flops_per_token(
        config, workload["seq_len"])
