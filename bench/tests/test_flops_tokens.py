"""The benchmark's copies of the program's FLOP arithmetic (the shared
part in ``harness/flops.py``, the dense model's in ``models/dense_swa.py``)
and token generator."""
import json

import numpy as np
import pytest

from harness import flops, tokens
from tiny import BENCH, model


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name, layers, gflop", [
    ("h2o-danube-1.8b.1chip", 2, 1.388),
    ("h2o-danube-1.8b.1chip", 4, 2.2845),
    ("h2o-danube-1.8b.4chip", 6, 3.181)])
def test_flops_per_token_match_the_hand_count(name, layers, gflop):
    # 81.92 M of LM head + 69.468 M of matmul parameters a layer, times 6,
    # + 31.45 M of causal attention a layer at seq 2048: 6 x 220.87 M +
    # 62.9 M at 2 layers, 6 x 359.79 M + 125.8 M at 4, 6 x 498.76 M +
    # 188.8 M at 6
    m = dict(config(name)["model"], n_layers=layers)
    assert model().train_flops_per_token(m, 2048) / 1e9 == pytest.approx(
        gflop, abs=5e-4)


def test_matmul_params_match_the_programs_count():
    from repro.configs import registry as REG
    from repro.utils.flops import param_counts

    m = config("h2o-danube-1.8b.1chip")["model"]
    cfg = REG.reduced_layers(REG.get_config("h2o-danube-1.8b"),
                             m["n_layers"])
    assert model().matmul_params(m) == param_counts(cfg)["active"]


def test_window_bounds_the_keys_per_token():
    assert flops.attention_keys_per_token(8, 0) == 4.5
    assert flops.attention_keys_per_token(8, 2) == (1 + 2 * 7) / 8


def test_generator_matches_the_programs_pipeline():
    from repro.data.synthetic import TokenPipeline

    traffic = json.loads((BENCH / "traffic" / "seq2048.json").read_text())
    traffic.update(seq_len=64, batch_per_agent=2)
    seed = 2**31 + 5
    ours = tokens.make_stream(traffic, 512, 3, seed)
    theirs = TokenPipeline(vocab=512, seq_len=64, batch_per_agent=2,
                           n_agents=3, seed=seed)
    for _ in range(3):
        a, b = next(ours), next(theirs)
        for k in ("tokens", "labels"):
            np.testing.assert_array_equal(a[k], b[k])
