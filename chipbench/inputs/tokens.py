"""Synthetic token rows: uniform ids over the configuration's
vocabulary, (rows, seq_len) int32."""

import jax
import jax.numpy as jnp


def make(key, config, workload, rows):
    return jax.random.randint(key, (rows, workload["seq_len"]), 0,
                              config["vocab_size"], jnp.int32)
