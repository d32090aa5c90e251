"""Device time per step of the operations in the name scopes
``consensus.*`` (the consensus mix), averaged over the chips; None where
the trace holds no such scope."""
from harness import scopes


def read(run):
    t = run["trace"]
    return None if t is None else scopes.per_step_ms(t).get("mix_ms")
