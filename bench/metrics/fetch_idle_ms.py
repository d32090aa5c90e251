"""Device-idle time per step while the host is inside the trainer's span
``train.metrics`` (the fetch of the step's metrics), averaged over the
chips; None where the trace holds no idle time inside that span."""
from harness import scopes


def read(run):
    t = run["trace"]
    return None if t is None else scopes.per_step_ms(t).get("fetch_idle_ms")
