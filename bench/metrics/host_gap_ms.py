"""Mean device-idle time between one execution of the step program and the
next, from the trace, averaged over the chips: the time the device waits
for the trainer loop (batch, dispatch, host sync)."""


def read(run):
    t = run["trace"]
    if t is None:
        return None
    per_chip = [sum(c["module_gaps_ns"]) / len(c["module_gaps_ns"])
                for c in t["chips"] if c["module_gaps_ns"]]
    if not per_chip:
        return None
    return sum(per_chip) / len(per_chip) * 1e-6
