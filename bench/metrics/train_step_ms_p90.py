"""90th percentile of the host-clock time of every step in the window; a
step runs from one batch request of the trainer to the next, so it ends
at the step's host sync."""
import numpy as np


def read(run):
    if not run["step_s"]:
        return None
    return float(np.percentile(np.asarray(run["step_s"]) * 1e3, 90))
