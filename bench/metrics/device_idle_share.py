"""1 - (union of device-operation intervals / traced window), averaged over
the chips."""


def read(run):
    t = run["trace"]
    if t is None or not t["chips"] or not t["window_ns"]:
        return None
    return 100.0 * (1.0 - t["busy_ns"] / t["window_ns"])
