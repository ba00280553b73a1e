"""Weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, in the layout and dtype the program
takes them (its parameter shapes, read with ``jax.eval_shape``), and
hands the same arrays to the program and to the reference: neither
takes anything the other made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _leaf(key, name: str, shape, dtype):
    if name == "scale":                      # norm gains around 1
        return (1.0 + 0.1 * jax.random.normal(key, shape)).astype(dtype)
    if name in ("b", "bias"):
        return jnp.zeros(shape, dtype)
    if name == "table":                      # embedding / LM head rows
        return (0.02 * jax.random.normal(key, shape)).astype(dtype)
    # a matrix (or a stack of them): fan-in scaling keeps activations O(1)
    return (jax.random.normal(key, shape) * shape[-2] ** -0.5).astype(dtype)


def make(shapes, seed: int):
    """A pytree like ``shapes`` (of ``jax.ShapeDtypeStruct``), drawn from
    ``seed``; leaf ``i`` from the key folded with ``i``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def build(key):
        return treedef.unflatten([
            _leaf(jax.random.fold_in(key, i), path[-1].key, s.shape, s.dtype)
            for i, (path, s) in enumerate(flat)])

    return build(jax.random.key(seed))
