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


def leaf_shapes(m: dict) -> dict:
    """``{path: (shape, fan_in)}`` of one agent's parameters; ``fan_in`` 0
    marks a norm scale, -1 the embedding table."""
    d, H, G, f, V, L = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["d_ff"], m["vocab"], m["n_layers"])
    hd = m.get("head_dim") or d // H
    shapes = {
        "embed/table": ((V, d), -1),
        "ln_f/scale": ((d,), 0),
        "blocks/ln1/scale": ((L, d), 0),
        "blocks/ln2/scale": ((L, d), 0),
        "blocks/attn/wq/w": ((L, d, H, hd), d),
        "blocks/attn/wk/w": ((L, d, G, hd), d),
        "blocks/attn/wv/w": ((L, d, G, hd), d),
        "blocks/attn/wo/w": ((L, H, hd, d), H * hd),
        "blocks/mlp/up/w": ((L, d, f), d),
        "blocks/mlp/down/w": ((L, f, d), f),
    }
    if m["gated_mlp"]:
        shapes["blocks/mlp/gate/w"] = ((L, d, f), d)
    if not m["tie_embeddings"]:
        shapes["lm_head/w"] = ((d, V), d)
    return shapes


def agent_params(key, m: dict) -> dict:
    """One agent's parameters (flat), in the configured parameter type."""
    dt = jnp.dtype(m["param_dtype"])
    out = {}
    for i, (path, (shape, fan_in)) in enumerate(
            sorted(leaf_shapes(m).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if fan_in == 0:
            v = 1.0 + 0.1 * z
        elif fan_in < 0:
            v = 0.02 * z
        else:
            v = z / np.sqrt(fan_in)
        out[path] = v.astype(dt)
    return out


def stacked_params(key, m: dict, n_agents: int) -> dict:
    """All agents' parameters, nested as the program keeps them, with the
    agent axis leading."""
    keys = jax.vmap(lambda a: jax.random.fold_in(key, a))(
        jnp.arange(n_agents))
    return tree.unflatten(jax.vmap(lambda k: agent_params(k, m))(keys))


def seed_key(seed: int):
    return jax.random.key(seed)
