"""Adapter: a decoder-only language model SERVED through the program's
continuous-batching path — ``TransformerConfig``, ``TransformerLM``'s
parameter tree, ``PagedKVPrograms`` (the bucketed prefill, ingest and
decode programs over the paged key/value pools) and
``ContinuousBatcher`` with its background tick thread, the deployment's
mode.  The HTTP front end is not in the path.  ``FLOW`` hands the run to
``chipbench/serve_run.py``.
"""

FLOW = "serve_run"


def program_config(config, workload):
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        max_seq_len=workload["server"]["max_seq_len"],
        attention_window=config["sliding_window"],
        rope_theta=config["rope_theta"],
        dtype=jnp.dtype(config["torch_dtype"]))


def param_shapes(config, workload):
    """The program's own parameter tree as shapes (nothing is run)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerLM

    tokens = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    return jax.eval_shape(
        lambda t: TransformerLM(program_config(config, workload)).init(
            jax.random.PRNGKey(0), t)["params"], tokens)


def make_programs(config, workload):
    from horovod_tpu.serving.kvcache import PagedKVPrograms

    server = workload["server"]
    return PagedKVPrograms(
        program_config(config, workload), max_slots=server["max_slots"],
        block_tokens=server["block_tokens"], n_blocks=server["n_blocks"],
        prompt_buckets=server["prompt_buckets"])


class Server:
    """The programs, warmed up, and the batcher with its tick thread
    running: what a replica is once it takes requests."""

    def __init__(self, config, workload, params, journal_path):
        from horovod_tpu.serving.continuous import ContinuousBatcher

        self.programs = make_programs(config, workload)
        self.warmed = self.programs.warmup(params)
        self.batcher = ContinuousBatcher(
            params, self.programs,
            max_new_tokens=workload["server"]["max_new_tokens"],
            journal_path=journal_path)
        self.batcher.start()

    def submit(self, prompt, budget, on_token):
        return self.batcher.submit(prompt, budget, on_token)

    def blocks_in_use(self):
        return self.batcher.pool.in_use

    def pools(self):
        return self.batcher.k_pool, self.batcher.v_pool

    def stop(self):
        """Drains what is queued and in flight, then ends the thread."""
        self.batcher.stop()


def program_names():
    """How a device trace names the three kinds of program."""
    return {"prefill": "_prefill_fwd", "ingest": "_ingest_fwd",
            "decode": "_decode_fwd"}


def cache_stats():
    """(hits, misses) of the program cache the serving programs share."""
    from horovod_tpu.ops.compiled import program_cache_stats

    return program_cache_stats()
