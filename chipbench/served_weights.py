"""Seeded weights of a served model, layer by layer: layer ``l`` draws
from ``fold_in(key, 1 + l)`` and what is not a layer's from
``fold_in(key, 0)``, each through ``weights.make`` and rounded to the
type the configuration serves in.  So the harness can make the stacked
tree on the device in one jitted call, in the served type, never holding
more than one layer in float32; and a reference can make one layer at a
time, in float32, with the same values."""

import jax
import jax.numpy as jnp

from chipbench import weights


def dtype_of(config):
    return jnp.dtype(config["torch_dtype"])


def make_top(key, top_spec, dtype):
    return jax.tree.map(lambda a: a.astype(dtype),
                        weights.make(jax.random.fold_in(key, 0), top_spec))


def make_layer(key, layer_spec, layer, dtype):
    return jax.tree.map(
        lambda a: a.astype(dtype),
        weights.make(jax.random.fold_in(key, 1 + layer), layer_spec))


def make_stacked(key, top_spec, layer_spec, layers, dtype):
    """{**top, "layers": every layer's leaves stacked on axis 0}."""
    stacked = jax.lax.map(
        lambda layer: make_layer(key, layer_spec, layer, dtype),
        jnp.arange(layers, dtype=jnp.int32))
    return {**make_top(key, top_spec, dtype), "layers": stacked}
