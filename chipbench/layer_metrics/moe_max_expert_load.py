"""The busiest expert's load against a balanced router's: the sum over
the routed layers of the tokens the busiest of ALL the router's experts
got (``horovod_moe_max_expert_tokens_total``, summed on the device
inside the step) over steps x layers x ranks x tokens x experts per
token / experts.  1.0 under a perfectly balanced router; it is the
straggler an expert-parallel group would wait for, held here or not."""

from chipbench import afmoe_flops, scope_join

COUNTERS = ["horovod_moe_max_expert_tokens_total"]


def read(ctx):
    total = scope_join.counter_delta(ctx, COUNTERS[0])
    if total <= 0:                  # a program without the sum
        return None
    config = ctx["config"]
    balanced = ctx["window"]["samples_per_step"] \
        * config["num_experts_per_tok"] / afmoe_flops.routed_width(config)
    return total / ctx["window"]["steps"] / config["num_hidden_layers"] \
        / balanced
