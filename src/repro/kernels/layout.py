"""The device's default layout of an array: the order of its dims in
memory and its tiling.  A view of a leaf that follows this order is a
bitcast, where one against it is a relayout copy (an HBM pass)."""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.layout import Layout


def default_layout(shape, dtype, device) -> Layout:
    """``device``'s default layout of an array: its dims' major-to-minor
    order and its tiling."""
    layout = device.client.get_default_layout(jnp.dtype(dtype), tuple(shape),
                                              device)
    return Layout.from_pjrt_layout(layout)
