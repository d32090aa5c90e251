"""Consensus collectives: stacked einsum, hierarchical, shard_map mapped."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import consensus as C, graph as G


def _state(n, shape=(3,), seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(n,) + shape), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(n, 2, 2)), jnp.float32)}


def test_mix_stacked_matches_numpy():
    W = G.metropolis_weights(G.ring(5, directed=False))
    x = _state(5)
    y = C.mix_stacked(x, W)
    for k in x:
        expect = np.einsum("ab,b...->a...", W, np.asarray(x[k]))
        np.testing.assert_allclose(np.asarray(y[k]), expect, rtol=1e-5,
                                   atol=1e-6)


def test_mix_stacked_uniform_shortcut():
    W = np.full((4, 4), 0.25)
    x = _state(4)
    y = C.mix_stacked(x, W)
    for k in x:
        expect = np.broadcast_to(np.asarray(x[k]).mean(0, keepdims=True),
                                 x[k].shape)
        np.testing.assert_allclose(np.asarray(y[k]), expect, rtol=1e-6)


def test_hierarchical_equals_kron_every_step():
    P, D = 2, 3
    Wp = G.xiao_boyd_weights(G.complete(P))
    Wi = G.metropolis_weights(G.complete(D))
    x = _state(P * D, seed=1)
    y = C.mix_hierarchical(x, Wi, Wp, jnp.int32(0), period=1)
    Wk = G.hierarchical_weights(Wp, Wi)
    for k in x:
        expect = np.einsum("ab,b...->a...", Wk, np.asarray(x[k]))
        np.testing.assert_allclose(np.asarray(y[k]), expect, rtol=1e-5,
                                   atol=1e-6)


def test_hierarchical_period_skips_cross_pod():
    P, D = 2, 2
    Wp = G.xiao_boyd_weights(G.complete(P))
    Wi = G.xiao_boyd_weights(G.complete(D))
    x = _state(P * D, seed=2)
    y = C.mix_hierarchical(x, Wi, Wp, jnp.int32(1), period=4)  # 1 % 4 != 0
    # intra-pod only: each pod's pair averaged, pods differ
    for k in x:
        arr = np.asarray(x[k]).reshape((P, D) + x[k].shape[1:])
        expect = np.broadcast_to(arr.mean(1, keepdims=True),
                                 arr.shape).reshape(x[k].shape)
        np.testing.assert_allclose(np.asarray(y[k]), expect, rtol=1e-5,
                                   atol=1e-6)


def test_iterated_mixing_reaches_consensus():
    W = G.uniform_weights(G.random_strongly_connected(6, 0.3, seed=4))
    x = _state(6, seed=3)
    for _ in range(200):
        x = C.mix_stacked(x, W)
    for k in x:
        arr = np.asarray(x[k])
        assert np.abs(arr - arr[0]).max() < 1e-4


# agent-stacked leaves of 4 agents, one a device: (shape, dtype, spec)
MESH_LEAVES = {
    "first_dim_splits": ((4, 64, 256), "bfloat16", ("data", None, "model")),
    "split_dim_not_first": ((4, 3, 40, 128), "bfloat16", ("data",)),
    "no_dim_divides": ((4, 3, 5, 7), "bfloat16", ("data",)),
    "float32": ((4, 64, 256), "float32", ("data",)),
}
EXCHANGED = ("first_dim_splits", "split_dim_not_first", "float32")


@pytest.fixture(scope="module")
def mixed_on_four_devices(tmp_path_factory):
    """``mix_uniform_constrained`` over four virtual CPU devices (set before
    JAX starts, hence the child process) on heavy-tailed leaves: per leaf
    the count of values that differ from the float32 mean rounded once,
    and the records of the mix's split."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import json, sys
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import consensus as C
        from repro.launch.mesh import make_host_mesh
        from repro.obs import metrics as obs_metrics
        leaves = json.loads(sys.argv[1])
        mesh = make_host_mesh()
        rng = np.random.default_rng(0)
        tree, specs = {}, {}
        for name, (shape, dtype, spec) in leaves.items():
            # heavy tails: a normal over a uniform squared, so the agents'
            # values lie decades apart and their f32 sum rounds
            x = (rng.standard_normal(shape)
                 / rng.uniform(0.05, 1.0, shape) ** 2)
            specs[name] = P(*spec)
            tree[name] = jax.device_put(
                jnp.asarray(x, dtype), NamedSharding(mesh, specs[name]))
        out = jax.jit(lambda t: C.mix_uniform_constrained(t, specs, mesh))(
            tree)
        sink = obs_metrics.MemorySink()
        prev = obs_metrics.set_sink(sink)
        C.record_uniform_split(tree, specs, mesh)
        obs_metrics.set_sink(prev)
        bits = {jnp.dtype(jnp.bfloat16): np.uint16,
                jnp.dtype(jnp.float32): np.uint32}
        differ = {}
        for name, x in tree.items():
            want = jnp.broadcast_to(
                jnp.mean(np.asarray(x).astype(np.float32), axis=0),
                x.shape).astype(x.dtype)
            got = np.asarray(out[name])
            differ[name] = [int(np.sum(
                got.view(bits[x.dtype]) != np.asarray(want).view(
                    bits[x.dtype]))), int(got.size)]
        print(json.dumps({"differ": differ, "records": sink.records}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        ["src"] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "-c", script,
                          json.dumps(MESH_LEAVES)], cwd=root, env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", [*MESH_LEAVES, "records"])
def test_mesh_mix_is_the_f32_mean_rounded_once(mixed_on_four_devices, case):
    """Across devices the uniform mix equals ``jnp.mean`` in float32 rounded
    once to the leaf's dtype, bit for bit, on both of its paths: the
    exchange where a split of the shard divides by the agents (bf16 and
    float32 leaves), the float32 all-reduce for a leaf whose no split
    divides.  The records count each path's parameters of one agent."""
    out = mixed_on_four_devices
    if case != "records":
        differ, size = out["differ"][case]
        assert size >= 4 * 105 and differ == 0, (case, differ, size)
        return
    records = {r["name"]: r for r in out["records"]}
    for name, on in (("consensus.exchange_params", True),
                     ("consensus.allreduce_params", False)):
        leaves = [k for k in MESH_LEAVES if (k in EXCHANGED) == on]
        assert records[name]["value"] == sum(
            int(np.prod(MESH_LEAVES[k][0][1:])) for k in leaves)
        assert records[name]["leaves"] == len(leaves)
