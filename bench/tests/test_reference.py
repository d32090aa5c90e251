"""The float32 reference against the program's own ``train_step`` at the
smoke size on the CPU, with the program run in float32 too: the two must
agree to float32 rounding, step by step, leaf by leaf and agent by agent.
A second test runs the program as configured (bfloat16) through the
benchmark's session and holds it to the cell's limits."""
import json
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from harness import check, reference, tokens, tree, weights


def program_f32_readings(m, tr, traffic, seed, steps=3):
    from repro.configs import registry as REG
    from repro.training.train_step import (TrainConfig, TrainState,
                                           build_optimizer, make_train_step)

    cfg = REG.get_smoke_config("h2o-danube-1.8b").replace(
        param_dtype="float32", compute_dtype="float32")
    tc = TrainConfig(optimizer="frodo", alpha=tr["alpha"], beta=tr["beta"],
                     lam=tr["lam"], T=tr["T"], memory_mode="expsum",
                     K=tr["K"], acc_dtype="float32", remat=False,
                     topology="complete")
    A = tr["agents"]
    step = jax.jit(make_train_step(cfg, tc, A))
    opt = build_optimizer(tc)
    key = weights.seed_key(seed)
    p0 = weights.stacked_params(key, tiny.model().leaf_shapes(m),
                                m["param_dtype"], A)
    state = TrainState(p0, opt.init(p0), jnp.zeros((), jnp.int32))
    stream = tokens.make_stream(traffic, m["vocab"], A, seed)
    rates, _ = reference.expsum_fit(tr["T"], tr["lam"], tr["K"])
    k = int(np.argmax(rates))
    out = {"losses": []}
    for t in range(steps):
        state, met = step(state, next(stream))
        out["losses"].append(float(met["loss"]))
        if t == 0:
            acc = tree.flatten(state.opt_state["acc"])
            out["first_grad"] = [
                {n: float(jnp.linalg.norm(v[k, a] / rates[k]))
                 for n, v in acc.items()} for a in range(A)]
    p, q = tree.flatten(state.params), tree.flatten(p0)
    out["change"] = [{n: float(jnp.linalg.norm(p[n][a] - q[n][a]))
                      for n in p} for a in range(A)]
    return out


@pytest.mark.parametrize("agents", [2, 4])
def test_reference_matches_the_float32_train_step(agents):
    m = dict(tiny.MODEL, param_dtype="float32", compute_dtype="float32")
    cfg = json.loads((tiny.BENCH / "configs" /
                      "h2o-danube-1.8b.1chip.json").read_text())
    cfg["model"] = m
    cfg["trainer"]["agents"] = agents
    traffic = json.loads((tiny.BENCH / "traffic" / "seq2048.json")
                         .read_text())
    traffic["seq_len"] = 128            # past the window of 64
    seed = 2**31 + 11
    prog = program_f32_readings(m, cfg["trainer"], traffic, seed)
    model = tiny.model()
    stacked = tree.flatten(weights.stacked_params(
        weights.seed_key(seed), model.leaf_shapes(m), m["param_dtype"],
        agents))
    devs = jax.devices()[:1]
    params = reference.agent_slices(stacked, agents, model.stacks(m), devs)
    stream = tokens.make_stream(traffic, m["vocab"], agents, seed)
    ref = reference.run(model, params, [next(stream) for _ in range(3)], cfg,
                        devs)
    gaps = check.compare(prog, ref)
    for name, (value, where) in gaps.items():
        assert value < 2e-5, (name, value, where)


def test_program_as_configured_is_within_the_cells_limits():
    from harness import runner

    with tempfile.TemporaryDirectory() as d:
        root = tiny.make_copy(Path(d))
        ses = runner.Session(root, tiny.CELL, require_accelerator=False)
        state, feed, prog, _ = ses.start(7)
        del state
        ok, lines = check.judge(check.compare(prog, ses.reference(7)),
                                ses.cell.limits)
    assert ok, lines
