"""Share of the chip's HBM peak that the FrODO update reaches: the bytes the
work under the name scope ``frodo.update`` has to move each step on a chip,
over the device time per step of that scope, over the chip's HBM bytes/s.

That work is the gradient clip's global norm (a read of the gradient, where
the configuration's ``grad_clip`` is on) and the exp-sum update (the
gradient read, the parameter read and written, each accumulator read and
written).  For each leaf of one agent (``leaf_shapes`` of the
configuration's model module) with n parameters of b_p bytes (the leaf's
dtype, the gradient's too) and K accumulators of b_acc bytes (the trainer's
``acc_dtype``):

    n * (c b_p + 3 b_p + 2 K b_acc),   c = 1 with the clip, else 0

times the agents, over the chips.  That is 24 B a parameter for bfloat16
parameters, the clip and K = 4 bfloat16 accumulators.  The scope is timed
whole, so the share holds whichever path computes the clip and the update:
the fused kernel or jnp.  Work of another layer moved into the scope (the
mix, say) adds its time and none of its bytes, so it lowers the share; it
then shows in that layer's own metric and end to end.  None without a
trace, a peak or the scope, or for a memory other than the exponential sum.
"""
import jax.numpy as jnp
import numpy as np

from harness import scopes, weights


def update_bytes(run) -> float:
    """Bytes the clip and the exp-sum update move each step on one chip."""
    cfg = run["config"]
    m, tr = cfg["model"], cfg["trainer"]
    b_acc = jnp.dtype(tr["acc_dtype"]).itemsize
    reads = 4 if cfg["grad_clip"] > 0 else 3
    per_agent = 0
    for entry in run["model"].leaf_shapes(m).values():
        shape, _, dt = weights.leaf(entry, m["param_dtype"])
        per_agent += int(np.prod(shape)) * (reads * dt.itemsize
                                             + 2 * tr["K"] * b_acc)
    return per_agent * tr["agents"] / run["chips"]


def read(run):
    t = run["trace"]
    ms = None if t is None else scopes.scope_ms(t, "frodo.update")
    if ms is None or not run["peaks"]:
        return None
    if run["config"]["trainer"]["memory_mode"] != "expsum":
        return None
    return 100.0 * update_bytes(run) / (ms * 1e-3) / run["peaks"][
        "hbm_bytes_per_s"]
