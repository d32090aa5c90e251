"""Model FLOP utilisation of the whole step: model FLOPs per token (6
N_active plus causal attention, forward and backward, no recompute; see
harness/flops.py) times the traced run's tokens/s, over the chips' summed
bf16 peak."""


def read(run):
    if run["trace"] is None or not run["steps"] or not run["peaks"]:
        return None
    rate = run["steps"] * run["tokens_per_step"] / run["window_s"]
    peak = run["chips"] * run["peaks"]["bf16_flops"]
    return 100.0 * run["flops_per_token"] * rate / peak
