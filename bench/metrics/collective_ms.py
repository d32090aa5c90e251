"""Device time per step in which a collective (all-reduce, all-gather,
collective-permute, reduce-scatter, all-to-all) is in flight, averaged over
the chips."""


def read(run):
    t = run["trace"]
    if t is None or not t["steps"]:
        return None
    per_chip = [c["collective_ns"] for c in t["chips"]]
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / t["steps"] * 1e-6
