"""The benchmark's own weights: random, from the seed, in the layout and the
type the program trains them in.

Every agent gets its own draw (the agents start apart, as FrODO's agents
do), and so does every leaf, norm scales included, so that the first
consensus mix moves every leaf.  The program and the reference are both
given these weights; neither makes its own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness import tree


def leaf(entry: tuple, dtype: str) -> tuple:
    """``(shape, fan_in, dtype)`` of one ``leaf_shapes`` entry: a leaf
    ``(shape, fan_in)`` is in the configuration's ``dtype``, a leaf
    ``(shape, fan_in, dtype)`` in its own (a router kept in float32)."""
    shape, fan_in, *own = entry
    return shape, fan_in, jnp.dtype(own[0] if own else dtype)


def agent_params(key, shapes: dict, dtype: str) -> dict:
    """One agent's parameters (flat), each in ``dtype`` or its own;
    ``shapes`` is the model's ``leaf_shapes``: ``{path: (shape, fan_in)}``
    or ``{path: (shape, fan_in, dtype)}`` (``leaf``), where ``fan_in`` 0
    marks a norm scale (1 + 0.1 z) and -1 an embedding table (0.02 z), and
    any other leaf is z / sqrt(fan_in)."""
    out = {}
    for i, (path, entry) in enumerate(sorted(shapes.items())):
        shape, fan_in, dt = leaf(entry, dtype)
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if fan_in == 0:
            v = 1.0 + 0.1 * z
        elif fan_in < 0:
            v = 0.02 * z
        else:
            v = z / np.sqrt(fan_in)
        out[path] = v.astype(dt)
    return out


def stacked_params(key, shapes: dict, dtype: str, n_agents: int) -> dict:
    """All agents' parameters, nested as the program keeps them, with the
    agent axis leading."""
    keys = jax.vmap(lambda a: jax.random.fold_in(key, a))(
        jnp.arange(n_agents))
    return tree.unflatten(jax.vmap(
        lambda k: agent_params(k, shapes, dtype))(keys))


def seed_key(seed: int):
    return jax.random.key(seed)
