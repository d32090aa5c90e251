"""Device time per step of the operations in the name scope
``train.fwd_bwd`` (forward, remat recompute, backward, LM head and loss),
averaged over the chips; None where the trace holds no such scope."""
from harness import scopes


def read(run):
    t = run["trace"]
    return None if t is None else scopes.per_step_ms(t).get("fwd_bwd_ms")
