"""Random weights from the seed, made on the device in one jitted call.

The tree's shapes and types are those the program serves (its abstract
parameter tree); the values are the benchmark's: each leaf is drawn
normal with the standard deviation the configuration's ``init`` gives for
its name, either a number or ``"fan_in"`` (one over the square root of the
leaf's second-to-last axis, the contraction axis of a weight matrix), and
0 means zeros. The reference takes the same tree, made here, not by the
program.
"""

from __future__ import annotations

import math


def make(abstract, init: dict, seed: int):
    import jax
    import jax.numpy as jnp

    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def std_of(path, leaf) -> float:
        name = str(getattr(path[-1], "key", path[-1]))
        rule = init[name]
        if rule == "fan_in":
            return 1.0 / math.sqrt(leaf.shape[-2])
        return float(rule)

    stds = [std_of(p, leaf) for p, leaf in paths]
    leaves = [leaf for _, leaf in paths]

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, leaf, std in zip(keys, leaves, stds):
            if std == 0.0:
                out.append(jnp.zeros(leaf.shape, leaf.dtype))
            else:
                x = jax.random.normal(k, leaf.shape, jnp.float32) * std
                out.append(x.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jax.random.key(seed))
