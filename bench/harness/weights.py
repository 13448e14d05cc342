"""Weights of a configuration, drawn on the device from the seed.

One jitted call draws every leaf straight into the served dtype. The tree
and its shapes are the configuration's family's (``weight_shapes`` of
``bench/reference/<family>.py``); the family's ``program_params`` hands the
program the same arrays in its own tree (``harness.model``), and its
reference reads them here. Neither makes weights of its own for the run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.spec import family


def dims(c: dict) -> dict:
    """The widths of configuration ``c`` under short names (``L D H Hkv Dh
    V E K F``), read through its family's ``KEYS``."""
    return {k: c[key] for k, key in family(c).KEYS.items()}


def make_weights(c: dict, seed: int):
    """Every weight of configuration ``c`` from ``seed``, on the default
    device, in the dtype it is served in, in one jitted call. Matrices are
    normal with std 1/sqrt(fan-in) (the embedding std 1); norm scales,
    every leaf whose path names a norm (``norm``, ``ln``), are 1 + N(0,
    0.1), so that a path that skipped a norm's scale would show."""
    dtype = jnp.dtype(c["torch_dtype"])
    shapes = family(c).weight_shapes(c)
    flat, tree = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda s: isinstance(s, tuple))[0]]

    def draw(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        keys = jax.random.split(key, len(flat))
        leaves = []
        for k, shape, path in zip(keys, flat, paths):
            if "norm" in path or "ln" in path:
                leaf = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            elif "unembed" in path:        # applied as h @ unembed.T
                leaf = jax.random.normal(k, shape, jnp.float32) * \
                    (shape[-1] ** -0.5)
            elif "embed" in path:
                leaf = jax.random.normal(k, shape, jnp.float32)
            else:                          # [..., in, out]
                leaf = jax.random.normal(k, shape, jnp.float32) * \
                    (shape[-2] ** -0.5)
            leaves.append(leaf.astype(dtype))
        return jax.tree_util.tree_unflatten(tree, leaves)

    # the seed enters as two 32-bit words: seeds wider than 32 bits differ
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return jax.jit(draw)(np.uint32(lo), np.uint32(hi))
