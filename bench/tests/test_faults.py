"""A run with the timed path broken underneath must come out not correct:
the harness's look for a chip is skipped, the program is broken as it
runs, and everything else of a run goes as on the chip (weights, first
steps, window, reference, comparison), at the smoke size on the CPU and
against the one-chip cell's limits, with two agents on one device and with
four agents over four (virtual) devices, where the mix is a collective.  Also the control: the reference with
int8 matmul operands in the program's place fails the limits."""
import tempfile
from pathlib import Path

import pytest

import tiny


def state_unchanged(real):
    def make(*a, **kw):
        step = real(*a, **kw)

        def broken(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return broken
    return make


def half_batch(real):
    def make(*a, **kw):
        step = real(*a, **kw)

        def broken(state, batch):
            labels = batch["labels"]
            keep = labels.shape[-1] // 2
            labels = labels.at[..., keep:].set(-1)
            return step(state, dict(batch, labels=labels))
        return broken
    return make


FAULTS = {"state_unchanged": ("repro.training.trainer.make_train_step",
                              state_unchanged),
          "half_batch": ("repro.training.trainer.make_train_step",
                         half_batch),
          "no_exchange": None}


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_step_is_not_correct(fault, chips, monkeypatch):
    import time

    from harness import runner
    import repro.core.consensus as C
    import repro.training.trainer as TR

    if fault == "no_exchange":
        monkeypatch.setattr(C, "mix_stacked", lambda x, W, **kw: x)
        monkeypatch.setattr(C, "mix_uniform_constrained",
                            lambda t, specs, mesh: t)
    else:
        target, wrap = FAULTS[fault]
        monkeypatch.setattr(TR, "make_train_step", wrap(TR.make_train_step))
    with tempfile.TemporaryDirectory() as d:
        root = tiny.make_copy(Path(d), chips=chips)
        result, checks, _ = runner.run(root, tiny.CELL, 2**31 + 21, 0.5,
                                       False, time.perf_counter(),
                                       require_accelerator=False)
    assert result["correct"] is False, checks
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("chips", [1, 4])
def test_sound_run_is_correct(chips):
    import time

    from harness import runner

    with tempfile.TemporaryDirectory() as d:
        root = tiny.make_copy(Path(d), chips=chips)
        result, checks, _ = runner.run(root, tiny.CELL, 2**31 + 21, 0.5,
                                       False, time.perf_counter(),
                                       require_accelerator=False)
    assert result["correct"] is True, checks
    assert set(result["metrics"]) == {"train_tokens_per_s",
                                      "train_step_ms_p90", "setup_s"}


def test_control_is_not_correct():
    """At the published widths (one layer, a 512-token vocabulary and
    sequence, so that the CPU holds it) the int8 control fails the one-chip
    cell's limits on every seed; at the smoke widths it does not always."""
    import json

    import jax

    import calibrate
    from harness import check, reference, tokens, tree, weights

    cfg = json.loads((tiny.BENCH / "configs" /
                      "h2o-danube-1.8b.1chip.json").read_text())
    cfg["model"].update(n_layers=1, vocab=512)
    traffic = json.loads((tiny.BENCH / "traffic" / "seq2048.json")
                         .read_text())
    traffic["seq_len"] = 512
    limits = json.loads((tiny.BENCH / "limits" /
                         "danube-1chip.seq2048.json").read_text())
    m, A, devs = cfg["model"], cfg["trainer"]["agents"], jax.devices()[:1]
    model = tiny.model()
    shapes = model.leaf_shapes(m)
    init = jax.jit(lambda k: weights.stacked_params(k, shapes,
                                                    m["param_dtype"], A))
    for seed in (3, 4, 5):
        out = {}
        for quant in (None, "int8"):
            stacked = tree.flatten(init(weights.seed_key(seed)))
            params = reference.agent_slices(stacked, A, model.stacks(m),
                                            devs)
            stream = tokens.make_stream(traffic, m["vocab"], A, seed)
            out[quant] = reference.run(
                model, params, [next(stream) for _ in range(3)], cfg, devs,
                quant=quant)
        ok, lines = check.judge(
            check.compare(calibrate.as_program(out["int8"]), out[None]),
            limits)
        assert not ok, (seed, lines)
