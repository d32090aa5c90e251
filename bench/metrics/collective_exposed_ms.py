"""The part of ``collective_ms`` during which no other operation runs on
that chip, per step, averaged over the chips."""


def read(run):
    t = run["trace"]
    if t is None or not t["steps"]:
        return None
    if not any(c["collective_ns"] for c in t["chips"]):
        return None
    per_chip = [c["exposed_ns"] for c in t["chips"]]
    return sum(per_chip) / len(per_chip) / t["steps"] * 1e-6
