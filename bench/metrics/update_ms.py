"""Device time per step of the operations in the name scope
``frodo.update`` (gradient clip, the FrODO memory and update), averaged over
the chips; None where the trace holds no such scope."""
from harness import scopes


def read(run):
    t = run["trace"]
    return None if t is None else scopes.per_step_ms(t).get("update_ms")
