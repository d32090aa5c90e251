"""FrODO optimizer semantics + equivalences."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines, loop, graph as G
from repro.core.frodo import FrodoConfig, apply_updates, frodo, memory_bytes


def _params():
    return {"a": jnp.asarray([1.0, -2.0, 3.0]),
            "b": {"w": jnp.ones((2, 2))}}


def _run_steps(opt, params, grads_seq):
    state = opt.init(params)
    out = []
    for g in grads_seq:
        delta, state = opt.update(g, state, params)
        params = apply_updates(params, delta)
        out.append(params)
    return out


def _grad_stream(n):
    rng = np.random.default_rng(0)
    p = _params()
    return [jax.tree.map(
        lambda x: jnp.asarray(rng.normal(size=x.shape), jnp.float32), p)
        for _ in range(n)]


def test_first_step_is_pure_gradient():
    """At k=1 there is no history: M=0, so x1 = x0 - alpha*g."""
    opt = frodo(FrodoConfig(alpha=0.5, beta=10.0, lam=0.2, T=4))
    p = _params()
    g = jax.tree.map(jnp.ones_like, p)
    delta, _ = opt.update(g, opt.init(p), p)
    expect = jax.tree.map(lambda x: -0.5 * jnp.ones_like(x), p)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6),
                 delta, expect)


def test_T1_is_heavy_ball_previous_gradient():
    """FrODO with T=1: M = g^(k-1) regardless of lambda."""
    gs = _grad_stream(4)
    p = _params()
    alpha, beta = 0.3, 0.2
    opt = baselines.heavy_ball(alpha, beta)
    state = opt.init(p)
    params = p
    prev_g = jax.tree.map(jnp.zeros_like, p)
    for g in gs:
        delta, state = opt.update(g, state, params)
        expect = jax.tree.map(lambda gg, pg: -(alpha * gg + beta * pg),
                              g, prev_g)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-6), delta, expect)
        params = apply_updates(params, delta)
        prev_g = g


def test_beta0_equals_no_memory():
    gs = _grad_stream(5)
    p = _params()
    o1 = frodo(FrodoConfig(alpha=0.4, beta=0.0, lam=0.2, T=8))
    o2 = baselines.no_memory(0.4)
    for a, b in zip(_run_steps(o1, p, gs), _run_steps(o2, p, gs)):
        jax.tree.map(lambda x, y: np.testing.assert_allclose(
            x, y, rtol=1e-6), a, b)


def test_expsum_tracks_exact():
    gs = _grad_stream(30)
    p = _params()
    cfg = dict(alpha=0.1, beta=0.05, lam=0.15, T=20)
    exact = _run_steps(frodo(FrodoConfig(**cfg, memory_mode="exact")), p, gs)
    approx = _run_steps(frodo(FrodoConfig(**cfg, memory_mode="expsum",
                                          K=10)), p, gs)
    for leafe, leafa in zip(jax.tree.leaves(exact[-1]),
                            jax.tree.leaves(approx[-1])):
        rel = (np.linalg.norm(leafe - leafa)
               / (np.linalg.norm(leafe) + 1e-9))
        assert rel < 0.05, rel


@pytest.mark.parametrize("mode", ["exact", "expsum"])
def test_kernel_path_matches_jnp_path(mode, pallas_interpret):
    """Pallas' interpret mode: the exact mode's kernel (``use_kernel``) and
    the exp-sum mode's fused ``apply`` against the jnp update."""
    gs = _grad_stream(6)
    p = _params()
    cfg = dict(alpha=0.3, beta=0.1, lam=0.2, T=5, memory_mode=mode, K=4)
    if mode == "expsum":
        # a matrix that the kernel tiles beside leaves that stay on jnp
        w = jnp.asarray(np.random.default_rng(1).normal(size=(2, 32, 128)),
                        jnp.float32)
        p = dict(p, w=w)
        gs = [dict(g, w=w * (i + 1) / 7) for i, g in enumerate(gs)]
    ref = _run_steps(frodo(FrodoConfig(**cfg)), p, gs)
    if mode == "exact":
        ker = _run_steps(frodo(FrodoConfig(**cfg, use_kernel=True)), p, gs)
    else:
        opt = frodo(FrodoConfig(**cfg))
        state, ker = opt.init(p), []
        for g in gs:
            p, state = opt.apply(g, state, p)
            ker.append(p)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-4, atol=1e-5), ref[-1], ker[-1])


def test_memory_bytes_accounting():
    """Thm 2.2: O(Tn) exact vs O(Kn) expsum."""
    p = _params()
    n_bytes = sum(x.size * 4 for x in jax.tree.leaves(p))
    assert memory_bytes(p, FrodoConfig(T=90)) == 90 * n_bytes
    assert memory_bytes(
        p, FrodoConfig(T=90, memory_mode="expsum", K=8)) == 8 * n_bytes


def test_adam_matches_reference_formula():
    p = {"x": jnp.asarray([1.0, 2.0])}
    g = {"x": jnp.asarray([0.1, -0.2])}
    opt = baselines.adam(1e-2)
    delta, st = opt.update(g, opt.init(p), p)
    # bias-corrected first step is exactly -lr * sign-ish g / (|g| + eps)
    np.testing.assert_allclose(
        np.asarray(delta["x"]),
        -1e-2 * np.asarray(g["x"]) / (np.abs(np.asarray(g["x"])) + 1e-8),
        rtol=1e-4)


def test_algorithm1_skips_update_at_k1():
    """loop.run: round 1 is consensus-only (Algorithm 1 'if k > 1')."""
    def objective(x, i):
        return 0.5 * jnp.sum(x ** 2)
    W = G.uniform_weights(G.complete(3), self_loop=False)
    x0 = jnp.asarray([[1.0, 0.0], [0.0, 1.0], [1.0, 1.0]])
    opt = baselines.no_memory(1e9)          # would explode if used at k=1
    out = loop.run(objective, x0, opt, W, 1, x_star=jnp.zeros(2))
    np.testing.assert_allclose(
        np.asarray(out["x"]), W @ np.asarray(x0), rtol=1e-6)
