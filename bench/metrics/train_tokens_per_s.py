"""Tokens of all agents trained in the window over the window's wall time
(host clock); every step of the window counts."""


def read(run):
    if not run["steps"]:
        return None
    return run["steps"] * run["tokens_per_step"] / run["window_s"]
