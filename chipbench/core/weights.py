"""Random weights made on the device from the seed, in one jitted call.

A configuration file's ``init`` maps a leaf name (the last key of its
path in the parameter tree) to a rule; ``"*"`` is the rule for every
other leaf:

* ``{"normal": s}``: normal with standard deviation ``s``;
  ``{"normal": "fan_in"}`` uses ``1 / sqrt(shape[-2])``;
* ``{"uniform": [lo, hi]}``.

Each leaf draws from the seed folded with its index in the flattened
tree, so the same seed gives the same weights.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def seed32(seed: int) -> int:
    """A 31-bit JAX seed from any whole number (the harness's seeds may
    exceed 32 bits)."""
    import numpy as np

    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def draw(rule: Dict, key: jax.Array, shape, dtype) -> jax.Array:
    if "normal" in rule:
        std = rule["normal"]
        if std == "fan_in":
            std = 1.0 / math.sqrt(shape[-2])
        x = jax.random.normal(key, shape, jnp.float32) * float(std)
    elif "uniform" in rule:
        lo, hi = rule["uniform"]
        x = jax.random.uniform(key, shape, jnp.float32, float(lo), float(hi))
    else:
        raise ValueError(f"unknown init rule {rule!r}")
    return x.astype(dtype)


def make(abstract, rules: Dict[str, Dict], seed: int, shardings=None):
    """A tree shaped like ``abstract`` (ShapeDtypeStructs), every leaf
    drawn by its rule, made by one jitted call and left on the device(s)
    ``shardings`` names (a tree of shardings or one sharding)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [_leaf_name(p) for p, _ in flat]
    for n in names:
        if n not in rules and "*" not in rules:
            raise KeyError(f"no init rule for parameter {n!r}")

    def gen(key):
        leaves = [draw(rules.get(n, rules.get("*")),
                       jax.random.fold_in(key, i), s.shape, s.dtype)
                  for i, (n, (_, s)) in enumerate(zip(names, flat))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    fn = jax.jit(gen, out_shardings=shardings)
    return jax.block_until_ready(fn(jax.random.PRNGKey(seed32(seed))))
