"""Trainer placement and entry-point plumbing: donated state, agents on a
mesh, the depth cut at published widths, the compile-cache location,
kernels that refuse to run off the TPU unless a test asks for interpret
mode, and the trainer's host spans on a profiler trace."""
import gc
import glob
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry as REG
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.train import build_trainer

SMALL = dict(smoke=True, agents=2, seq=16, batch_per_agent=1,
             memory_mode="expsum", K=2, acc_dtype="bfloat16")


def _batch(agents, seq, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(1, 512, (agents, 1, seq)).astype(np.int32)
    return {"tokens": toks, "labels": toks}


def test_step_donates_the_state():
    trainer = build_trainer(**SMALL)
    state = trainer.init(seed=0)
    leaf = jax.tree.leaves(state.params)[0]
    new_state, _ = trainer.step_fn(state, _batch(2, 16))
    assert leaf.is_deleted()
    assert not jax.tree.leaves(new_state.params)[0].is_deleted()


def test_trainer_spans_land_on_the_profiler_trace(tmp_path):
    """A profiler trace of ``Trainer.run`` carries the step annotation, the
    trainer's phase spans and a collection inside the batch fetch, all on
    the host plane; the collection hook is gone after the run."""
    from jax.profiler import ProfileData

    trainer = build_trainer(**SMALL)
    state = trainer.init(seed=0)

    def data():
        for i in range(3):
            if i == 1:
                gc.collect()
            yield _batch(2, 16, seed=i)

    hooks = list(gc.callbacks)
    with jax.profiler.trace(str(tmp_path)):
        trainer.run(state, data(), 3)
    assert gc.callbacks == hooks
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [(e.name, e.start_ns, e.end_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    count = {}
    for name, _, _ in events:
        count[name] = count.get(name, 0) + 1
    for name in ("train", "train.step", "train.data", "train.device_step",
                 "train.metrics", "train.log"):
        assert count.get(name) == 3, (name, count.get(name))
    fetches = [(s, e) for n, s, e in events if n == "train.data"]
    assert any(s <= g0 and g1 <= e for n, g0, g1 in events
               if n == "gc.gen2" for s, e in fetches)


def test_mesh_trainer_matches_plain_trainer():
    """On this host's devices, the placed step computes what the unplaced
    one does."""
    plain = build_trainer(**SMALL)
    placed = build_trainer(**SMALL, mesh=make_host_mesh())
    s1, s2 = plain.init(seed=3), placed.init(seed=3)
    for i in range(2):
        batch = _batch(2, 16, seed=i)
        s1, m1 = plain.step_fn(s1, batch)
        s2, m2 = placed.step_fn(s2, batch)
        np.testing.assert_allclose(np.asarray(m2["agent_loss"]),
                                   np.asarray(m1["agent_loss"]), rtol=1e-5)
    leaf = jax.tree.leaves(s2.params)[0]
    assert leaf.sharding.spec[0] == "data"


def test_agents_spread_over_four_host_devices(tmp_path):
    """Four virtual CPU devices (set before JAX starts, hence the child
    process): one agent per device, same per-agent losses as one device,
    agents with bit-identical parameters after the mix, and a mix that
    moves each agent's own bytes: an exchange of the leaves whose only
    all-gathers are one a leaf, each of one agent's elements (a gather of
    the agent dim holds A x those), no all-reduce in the mix as large as a
    parameter leaf (the float32 path's), and its split recorded once."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import json, re
        import jax, numpy as np
        from repro.launch.train import run_training
        from repro.obs import metrics as obs_metrics
        kw = dict(smoke=True, agents=4, steps=2, seq=16, batch_per_agent=1,
                  memory_mode="expsum", K=2, acc_dtype="bfloat16")
        losses = {}
        sink = obs_metrics.MemorySink()
        for mesh in (False, True):
            out = os.path.join(os.environ["OUT"], f"m{mesh}.jsonl")
            prev = obs_metrics.set_sink(sink if mesh else None)
            trainer, state = run_training(mesh=mesh, metrics_out=out, **kw)
            obs_metrics.set_sink(prev)
            losses[mesh] = [json.loads(l)["agent_loss"] for l in open(out)]
        apart = max(float(np.abs(np.asarray(sh.data, np.float32)
                                 - np.asarray(leaf.addressable_shards[0].data,
                                              np.float32)).max())
                    for leaf in jax.tree.leaves(state.params)
                    for sh in leaf.addressable_shards)
        per_dev = {}
        for leaf in jax.tree.leaves(state):
            for sh in leaf.addressable_shards:
                per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \\
                    + sh.data.nbytes
        batch = {k: np.zeros((4, 1, 16), np.int32)
                 for k in ("tokens", "labels")}
        hlo = trainer.step_fn.lower(state, batch).compile().as_text()

        def results(op, scope=""):
            # the result shapes of every ``op`` (a tuple's each) whose
            # op_name holds ``scope``
            return [[int(n) for n in dims.split(",") if n]
                    for m in re.finditer(r"= (\\(.*?\\)|\\S+) " + op
                                         + r"(-start)?\\(.*", hlo)
                    if scope in m.group(0)
                    for dims in re.findall(r"\\w+\\[([\\d,]*)\\]",
                                           m.group(1))]
        print(json.dumps({
            "losses": {str(k): v for k, v in losses.items()},
            "per_dev": list(per_dev.values()),
            "total": sum(l.nbytes for l in jax.tree.leaves(state)),
            "apart": apart,
            "leaves": [[list(l.shape), str(l.dtype)]
                       for l in jax.tree.leaves(state.params)],
            "all_to_all": len(results("all-to-all")),
            "mix_all_reduce": results("all-reduce", "consensus."),
            "all_gather": results("all-gather"),
            "split": [r for r in sink.records
                      if r["name"].startswith("consensus.")]}))
    """)
    env = dict(os.environ, OUT=str(tmp_path), JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   ["src"] + [p for p in [os.environ.get("PYTHONPATH")]
                              if p]))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "-c", script], cwd=root, env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    np.testing.assert_allclose(out["losses"]["True"], out["losses"]["False"],
                               rtol=1e-2, atol=1e-2)
    assert len(out["per_dev"]) == 4
    assert max(out["per_dev"]) <= out["total"] / 4 * 1.001
    assert out["apart"] == 0
    one_agent = [int(np.prod(shape[1:])) for shape, _ in out["leaves"]]
    assert out["all_to_all"] > 0
    # one all-gather a leaf, of the mean: one agent's elements, never A x
    assert sorted(int(np.prod(r)) for r in out["all_gather"]) == sorted(
        one_agent), out["all_gather"]
    assert not [r for r in out["mix_all_reduce"]
                if np.prod(r) >= min(one_agent)], out["mix_all_reduce"]
    assert [(r["name"], r["value"], r["leaves"]) for r in out["split"]] == [
        ("consensus.exchange_params", sum(one_agent), len(one_agent)),
        ("consensus.allreduce_params", 0, 0)]


def test_layer_cut_keeps_published_widths():
    full = REG.get_config("h2o-danube-1.8b")
    cfg = build_trainer(smoke=False, layers=2).cfg
    assert cfg.n_layers == 2
    assert cfg.replace(n_layers=full.n_layers) == full


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    calls = []
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    path = compile_cache.use_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]


def test_compile_cache_leaves_the_env_var_to_jax(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert calls == []


def test_kernel_refuses_to_run_off_tpu_unless_asked():
    from repro.kernels import frodo_update as kfu
    g = jnp.ones((2, 16, 128), jnp.float32)
    acc = jnp.zeros((2, 2, 16, 128), jnp.float32)
    with pytest.raises(ValueError, match="interpret"):
        kfu.expsum_apply(g, acc, g, jnp.float32(1), rates=(0.5, 0.9),
                         coeffs=(0.5, 0.9), alpha=0.1, beta=0.01)


def test_fused_train_step_matches_jnp_step():
    """Two steps with the exp-sum update as the fused kernel (Pallas'
    interpret mode) against the same steps with the jnp update, to bf16
    precision; the matrices go through the kernel, the norms through jnp."""
    from jax.experimental.pallas import tpu as pltpu
    batches = [_batch(2, 16, seed=i) for i in range(2)]
    plain = build_trainer(**SMALL)
    s1 = plain.init(seed=5)
    assert "pallas_call" not in str(jax.make_jaxpr(plain.step_fn)(
        s1, batches[0]))
    with pltpu.force_tpu_interpret_mode():
        fused = build_trainer(**SMALL)
        s2 = fused.init(seed=5)
        jaxpr = str(jax.make_jaxpr(fused.step_fn)(s2, batches[0]))
        assert jaxpr.count("frodo_expsum_apply") >= 4
        for batch in batches:
            s2, m2 = fused.step_fn(s2, batch)
            s1, m1 = plain.step_fn(s1, batch)
            np.testing.assert_allclose(np.asarray(m2["agent_loss"]),
                                       np.asarray(m1["agent_loss"]),
                                       rtol=1e-2)
    for a, b in zip(jax.tree.leaves(s2), jax.tree.leaves(s1)):
        x, y = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(x - y) <= 1e-2 * np.linalg.norm(y) + 1e-6
