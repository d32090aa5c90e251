"""A configuration brings its model as a file of its own
(``bench/models/<model_code>.py``) and the harness takes it by name.

* ``dense_swa`` through the harness gives, at the smoke size, the weights,
  the FLOP count and the reference's readings recorded from the harness
  before the model was split from it (``data/dense_swa.golden.json``), bit
  for bit.
* A toy module with two layer stacks (``toy_two_stack.py``), added as new
  files to a copy of the benchmark, runs through the weights, the FLOP
  count and the reference with no harness file changed; its router, a
  leaf that states float32 as its own dtype, is drawn, checked and
  compared in float32 while the others stay in the configuration's
  bfloat16.
* A leaf table that states the router's float32, as the program keeps a
  mixture of experts' router, fits the program's parameter tree in the
  runner's check; one that leaves every leaf to the configuration's dtype
  does not.
* No harness file names a model's leaves, sizes or attention kind.
"""
import hashlib
import json
import re
import shutil

import jax
import numpy as np
import pytest

import tiny
from harness import reference, runner, spec, tokens, tree, weights
from test_spec_cli import digests

GOLDEN = json.loads((tiny.BENCH / "tests/data/dense_swa.golden.json")
                    .read_text())
TOY = {"d_model": 64, "vocab": 96, "n_dense_layers": 1, "n_layers": 2,
       "d_ff_dense": 160, "d_ff": 48, "n_experts": 4,
       "param_dtype": "bfloat16"}


def smoke_config(m: dict, agents: int = 2) -> dict:
    cfg = json.loads((tiny.BENCH / "configs" / "h2o-danube-1.8b.1chip.json")
                     .read_text())
    cfg["model"] = m
    cfg["trainer"]["agents"] = agents
    return cfg


def stream(m: dict, agents: int, seed: int, seq_len: int, steps: int = 3):
    traffic = json.loads((tiny.BENCH / "traffic" / "seq2048.json")
                         .read_text())
    traffic["seq_len"] = seq_len
    s = tokens.make_stream(traffic, m["vocab"], agents, seed)
    return [next(s) for _ in range(steps)]


def stacked(model, m: dict, agents: int, seed: int) -> dict:
    return tree.flatten(jax.jit(lambda k: weights.stacked_params(
        k, model.leaf_shapes(m), m["param_dtype"], agents))(
            weights.seed_key(seed)))


def test_dense_swa_weights_are_the_golden_ones():
    flat = stacked(tiny.model(), tiny.MODEL, GOLDEN["agents"],
                   GOLDEN["seed"])
    got = {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest()
           for k, v in sorted(flat.items())}
    assert got == GOLDEN["weights"]


def test_dense_swa_flops_per_token_are_the_golden_ones():
    got = {"tiny@128": tiny.model().train_flops_per_token(tiny.MODEL, 128)}
    for name in ("h2o-danube-1.8b.1chip", "h2o-danube-1.8b.4chip"):
        m = json.loads((tiny.BENCH / "configs" / f"{name}.json")
                       .read_text())["model"]
        got[f"{name}@2048"] = tiny.model().train_flops_per_token(m, 2048)
    assert got == GOLDEN["flops_per_token"]


@pytest.mark.parametrize("case, kw", [
    ("plain", {}), ("int8", {"quant": "int8"}),
    ("half_batch", {"fault": "half_batch"})])
def test_dense_swa_reference_readings_are_the_golden_ones(case, kw):
    model, m = tiny.model(), tiny.MODEL
    A, seed = GOLDEN["agents"], GOLDEN["seed"]
    devs = jax.devices()[:1]
    params = reference.agent_slices(stacked(model, m, A, seed), A,
                                    model.stacks(m), devs)
    got = reference.run(model, params, stream(m, A, seed, 128),
                        smoke_config(dict(m)), devs, **kw)
    assert json.loads(json.dumps(got)) == GOLDEN["reference"][case]


def toy_copy(dest):
    """A copy of the benchmark with the toy model brought as new files: its
    module, its configuration and a cell."""
    tiny.make_copy(dest)
    shutil.copy(tiny.BENCH / "tests" / "toy_two_stack.py",
                dest / "bench/models/toy_two_stack.py")
    cfg = smoke_config(dict(TOY))
    cfg.update(name="toy", model_code="toy_two_stack")
    (dest / "bench/configs/toy.json").write_text(json.dumps(cfg))
    bench = json.loads((dest / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "a test",
                             "file": "bench/configs/toy.json",
                             "reduced": [], "why": "two stacks"})
    bench["workloads"].append({"name": "toy.seq128", "config": "toy",
                               "traffic": "seq128", "chips": 1,
                               "why": "two stacks"})
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copy(dest / f"bench/limits/{tiny.CELL}.json",
                dest / "bench/limits/toy.seq128.json")
    return dest


def test_a_model_with_two_stacks_runs_through_the_harness(tmp_path):
    before = digests(tiny.ROOT / "bench")
    root = toy_copy(tmp_path)
    after = digests(root / "bench")
    assert [p for p, h in before.items() if after.get(p) != h] == []

    cell = spec.Cell(root, "toy.seq128")
    model, m, A = cell.model, cell.config["model"], 2
    assert model.stacks(m) == {"dense_blocks": 1, "blocks": 2}
    shapes = model.leaf_shapes(m)
    flat = stacked(model, m, A, 2**31 + 7)
    assert {k: (v.shape, str(v.dtype)) for k, v in flat.items()} == {
        k: ((A, *e[0]), "float32" if k == "blocks/router/w" else "bfloat16")
        for k, e in shapes.items()}
    assert flat["dense_blocks/mlp/up/w"].shape[-1] == TOY["d_ff_dense"]
    # 6 N over every matmul leaf and norm scale, the embedding out
    n = sum(int(np.prod(e[0])) for e in shapes.values() if e[1] >= 0)
    assert model.train_flops_per_token(m, 128) == 6.0 * n

    devs = jax.devices()[:1]
    params = reference.agent_slices(flat, A, model.stacks(m), devs)
    assert sorted(k.split("/")[0] for k in params[0]
                  if "/mlp/up/" in k) == ["blocks.0", "blocks.1",
                                          "dense_blocks.0"]
    assert params[0]["blocks.1/router/w"].dtype == np.float32
    out = reference.run(model, params, stream(m, A, 2**31 + 7, 128),
                        cell.config, devs)
    assert np.isfinite(out["losses"]).all()
    for readings in (out["first_grad"], out["change"]):
        assert [set(r) for r in readings] == [set(shapes)] * A
        assert all(v > 0 for r in readings for v in r.values())


def test_split_and_join_layers_round_trip_two_stacks():
    import toy_two_stack as toy

    m = dict(TOY)
    flat = {k: np.asarray(v[0], np.float64) for k, v in
            stacked(toy, m, 1, 3).items()}
    split = reference.split_layers(flat, toy.stacks(m))
    # three leaves a stack and the router of blocks: the one layer of
    # dense_blocks stays one, the two of blocks become two
    assert len(split) == len(flat) + 4
    norms = reference.join_layers(
        {k: float(np.linalg.norm(v)) for k, v in split.items()},
        toy.stacks(m))
    assert norms.keys() == flat.keys()
    for k, v in flat.items():
        assert norms[k] == pytest.approx(float(np.linalg.norm(v)),
                                         rel=1e-12)


def test_a_float32_leaf_fits_the_program_tree():
    """The program's mixture of experts (qwen3-moe-30b-a3b at its smoke
    size) keeps its router in float32 and every other leaf in bfloat16."""
    from repro.launch.train import build_trainer
    from repro.training.train_step import abstract_train_state

    trainer = build_trainer(arch="qwen3-moe-30b-a3b", smoke=True, agents=2,
                            memory_mode="expsum", K=4, acc_dtype="bfloat16")
    c = trainer.cfg
    want = abstract_train_state(c, trainer.tc, 2).params
    L, d, V, H, G, hd = (c.n_layers, c.d_model, c.vocab, c.n_heads,
                         c.n_kv_heads, c.hd())
    E, f = c.moe.n_experts, c.moe.expert_d_ff
    shapes = {
        "embed/table": ((V, d), -1), "ln_f/scale": ((d,), 0),
        "lm_head/w": ((d, V), d),
        "blocks/ln1/scale": ((L, d), 0), "blocks/ln2/scale": ((L, d), 0),
        "blocks/attn/q_norm/scale": ((L, hd), 0),
        "blocks/attn/k_norm/scale": ((L, hd), 0),
        "blocks/attn/wq/w": ((L, d, H, hd), d),
        "blocks/attn/wk/w": ((L, d, G, hd), d),
        "blocks/attn/wv/w": ((L, d, G, hd), d),
        "blocks/attn/wo/w": ((L, H, hd, d), H * hd),
        "blocks/moe/experts/gate": ((L, E, d, f), d),
        "blocks/moe/experts/up": ((L, E, d, f), d),
        "blocks/moe/experts/down": ((L, E, f, d), f),
        "blocks/moe/router/w": ((L, d, E), d, "float32"),
    }

    def have(table):
        return jax.eval_shape(lambda k: weights.stacked_params(
            k, table, c.param_dtype, 2), weights.seed_key(0))

    assert runner.fits(want, have(shapes))
    shapes["blocks/moe/router/w"] = shapes["blocks/moe/router/w"][:2]
    assert not runner.fits(want, have(shapes))


LEAF = re.compile(r"attn/|mlp/|\bblocks[/.]|embed/|lm_head|ln_f|ln1|ln2|"
                  r"\"swa\"|attn_type|\bn_layers\b|\bn_heads\b|\bd_ff\b|"
                  r"\bhead_dim\b")


@pytest.mark.parametrize("path", sorted(
    p.name for p in (tiny.BENCH / "harness").glob("*.py")))
def test_the_harness_names_no_model_leaf(path):
    text = (tiny.BENCH / "harness" / path).read_text()
    found = [(i + 1, line.strip()) for i, line in
             enumerate(text.splitlines()) if LEAF.search(line)]
    assert found == []
