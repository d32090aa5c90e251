"""Device time per step of the operations of the step in no name scope,
averaged over the chips; None where the trace holds no ``train.fwd_bwd``
scope (a program without the step's scopes)."""
from harness import scopes


def read(run):
    t = run["trace"]
    return None if t is None else scopes.per_step_ms(t).get("unscoped_ms")
