"""Seeded weights, made by the benchmark (never by the program): one
traceable function from a key and a tree of leaf specifications, so the
harness and a reference can each make the same float32 parameters on
the device inside one jitted call."""

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class Leaf(NamedTuple):
    shape: Tuple[int, ...]
    kind: str = "normal"        # "normal" | "ones" | "zeros"
    std: float = 1.0


def _is_leaf(x):
    return isinstance(x, Leaf)


def seed_key(seed):
    """A key from any whole number a driver may pass (more than 32
    signed bits hold)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def make(key, spec):
    """The parameter tree of ``spec`` (a nested dict of ``Leaf``);
    leaf i draws from ``fold_in(key, i)`` in flattening order."""
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)
    out = []
    for i, leaf in enumerate(leaves):
        shape = tuple(leaf.shape)
        if leaf.kind == "normal":
            out.append(leaf.std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32))
        elif leaf.kind == "ones":
            out.append(jnp.ones(shape, jnp.float32))
        elif leaf.kind == "zeros":
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            raise ValueError(f"unknown leaf kind {leaf.kind!r}")
    return jax.tree.unflatten(treedef, out)


def shapes(spec):
    """{path: shape} of a spec or of a tree of arrays, for comparing
    the reference's tree with the program's."""
    flat = jax.tree_util.tree_leaves_with_path(spec, is_leaf=_is_leaf)
    return {jax.tree_util.keystr(path): tuple(leaf.shape)
            for path, leaf in flat}


def leaf_norms(tree):
    """{path: l2 norm in float32} (traceable)."""
    flat = jax.tree_util.tree_leaves_with_path(tree)
    return {jax.tree_util.keystr(path):
            jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for path, leaf in flat}
