"""Per-kernel allclose vs the pure-jnp oracle: shape x dtype sweeps +
hypothesis property tests, in Pallas' TPU interpret mode on the CPU (the
``pallas_interpret`` fixture of conftest.py).

``hypothesis`` is an optional dev dependency (requirements-dev.txt): the
sweep tests always run; the property tests only materialize when it is
installed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:          # property tests below are conditionally defined
    hypothesis = None

from repro.core import memory as fmem
from repro.kernels import ops, ref

pytestmark = pytest.mark.usefixtures("pallas_interpret")

SHAPES = [(128,), (1000,), (64, 33), (7,), (3, 5, 11), (2048,), (1,)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_exact_kernel_sweep(shape, dtype):
    rng = np.random.default_rng(hash((shape, str(dtype))) % 2 ** 31)
    T = 9
    g = jnp.asarray(rng.normal(size=shape), dtype)
    hist = jnp.asarray(rng.normal(size=(T,) + shape), dtype)
    w = jnp.asarray(fmem.mu_weights(T, 0.15), jnp.float32)
    for cursor in (0, 3, T - 1):
        d1, h1 = ops.frodo_update(g, hist, jnp.int32(cursor), w, 0.8, 0.35)
        d2, h2 = ref.frodo_update_ref(g, hist, jnp.int32(cursor), w,
                                      0.8, 0.35)
        np.testing.assert_allclose(np.asarray(d1, np.float32),
                                   np.asarray(d2, np.float32), **_tol(dtype))
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))


# exp-sum leaves: blocks of at most 32 x 384 parameters, so the first leaf
# takes two row blocks of 32 and the second three of 16
EXPSUM_LEAVES = [(2, 64, 256), (2, 3, 48, 384)]
EXPSUM_BLOCK_PARAMS = 32 * 384
EXPSUM_STATE = [(4, "bfloat16"), (8, "float32")]
ALPHA, BETA = 0.8, 0.35


def _expsum_opt(K, acc_dtype, **kw):
    from repro.core.frodo import FrodoConfig, frodo
    return frodo(FrodoConfig(alpha=ALPHA, beta=BETA, lam=0.15, T=40,
                             memory_mode="expsum", K=K, acc_dtype=acc_dtype,
                             **kw))


def _leaf_stream(shape, n, seed):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
            for _ in range(n + 1)]


@pytest.mark.parametrize("steps", [1, 3])
@pytest.mark.parametrize("scale", [1.0, 0.3])
@pytest.mark.parametrize("K,acc_dtype", EXPSUM_STATE)
@pytest.mark.parametrize("shape", EXPSUM_LEAVES)
def test_expsum_kernel_sweep(shape, K, acc_dtype, scale, steps, monkeypatch):
    """``Optimizer.apply`` runs the fused kernel (interpreted) and matches
    the jnp update (``update`` on the scaled gradient, then
    ``apply_updates``) to bf16 precision after one and three steps."""
    from repro.core.frodo import apply_updates
    from repro.kernels import frodo_update as kfu
    per_param = kfu.param_bytes(K, jnp.bfloat16, acc_dtype)
    monkeypatch.setattr(kfu, "BLOCK_BYTES", EXPSUM_BLOCK_PARAMS * per_param)
    assert kfu.expsum_block(shape, per_param) == (
        (32, 256) if shape[-2] == 64 else (16, 384))
    p0, *grads = _leaf_stream(shape, steps, hash(shape) % 2 ** 31)
    opt = _expsum_opt(K, acc_dtype)
    p_ker = p_jnp = {"w": p0}
    s_ker = s_jnp = opt.init(p_jnp)
    for g in grads:
        p_ker, s_ker = opt.apply({"w": g}, s_ker, p_ker, jnp.float32(scale))
        delta, s_jnp = opt.update({"w": (g * scale).astype(g.dtype)}, s_jnp,
                                  p_jnp)
        p_jnp = apply_updates(p_jnp, delta)
    for ker, jnp_ in ((p_ker["w"], p_jnp["w"]),
                      (s_ker["acc"]["w"], s_jnp["acc"]["w"])):
        # the jnp path rounds to bf16 between its operations, the kernel
        # once: compare in norm, over the whole leaf and its last row block
        for part in (np.s_[...], np.s_[..., -16:, :]):
            x = np.asarray(ker, np.float32)[part]
            y = np.asarray(jnp_, np.float32)[part]
            assert np.linalg.norm(x - y) <= 1e-2 * np.linalg.norm(y)
    assert int(s_ker["step"]) == steps


@pytest.mark.parametrize("order", [None, (0, 2, 1)], ids=["rowmajor", "minor"])
@pytest.mark.parametrize("K,acc_dtype", EXPSUM_STATE)
def test_expsum_kernel_matches_f32_oracle(K, acc_dtype, order):
    """One pass against ``ref.frodo_expsum_apply_ref`` (float32, each output
    rounded once).  ``order`` (0, 2, 1) runs the kernel on the transposed
    view that a device layout with the middle dim minor-most asks for."""
    from repro.kernels import frodo_update as kfu
    shape = (2, 384, 40)
    rng = np.random.default_rng(K)
    g = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    acc = jnp.asarray(rng.normal(size=(K,) + shape), acc_dtype)
    p = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    rates, coeffs = fmem.fit_expsum(40, 0.15, K)
    a1, p1 = kfu.expsum_apply(g, acc, p, jnp.float32(0.3), rates=rates,
                              coeffs=coeffs, alpha=ALPHA, beta=BETA,
                              order=order)
    a2, p2 = ref.frodo_expsum_apply_ref(g, acc, p, 0.3, rates, coeffs, ALPHA,
                                        BETA)
    assert a1.dtype == acc.dtype and p1.dtype == p.dtype
    ulp = 2 ** -7 if acc_dtype == "bfloat16" else 1e-6
    np.testing.assert_allclose(np.asarray(a1, np.float32),
                               np.asarray(a2, np.float32), rtol=ulp, atol=ulp)
    np.testing.assert_allclose(np.asarray(p1, np.float32),
                               np.asarray(p2, np.float32), rtol=2 ** -7,
                               atol=2 ** -7)


def test_expsum_kernel_writes_in_place():
    """The accumulators and the parameter are the kernel's outputs through
    ``input_output_aliases``: written over their own buffers."""
    from repro.kernels import frodo_update as kfu
    shape = (2, 64, 256)
    rates, coeffs = fmem.fit_expsum(40, 0.15, 4)
    g = jnp.zeros(shape, jnp.bfloat16)
    acc = jnp.zeros((4,) + shape, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda g, a, p: kfu.expsum_apply(
        g, a, p, jnp.float32(1), rates=rates, coeffs=coeffs, alpha=ALPHA,
        beta=BETA))(g, acc, g)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    # operands: scale, g, acc, p; results: acc, p
    assert tuple(calls[0].params["input_output_aliases"]) == ((2, 0), (3, 1))


def test_expsum_norm_scale_leaf_stays_on_jnp():
    """A leaf with fewer than 16 rows (a norm scale) takes the jnp update,
    the matrix beside it the kernel, and the split is logged once."""
    from repro import obs
    from repro.core.frodo import apply_updates
    from repro.kernels import frodo_update as kfu
    assert kfu.expsum_block((2, 2560), 22) is None
    assert kfu.expsum_block((2, 4, 2560), 22) is None
    p = {"scale": jnp.ones((2, 2560), jnp.bfloat16),
         "w": jnp.ones((2, 64, 256), jnp.bfloat16)}
    g = jax.tree.map(lambda x: jnp.full(x.shape, 0.5, x.dtype), p)
    opt = _expsum_opt(4, "bfloat16")
    sink = obs.MemorySink()
    prev = obs.set_sink(sink)
    try:
        s = opt.init(p)
        new_p, new_s = opt.apply(g, s, p, None)
        opt.apply(g, new_s, new_p, None)
    finally:
        obs.set_sink(prev)
    delta, jnp_s = opt.update(g, s, p)
    jnp_p = apply_updates(p, delta)
    np.testing.assert_array_equal(np.asarray(new_p["scale"], np.float32),
                                  np.asarray(jnp_p["scale"], np.float32))
    np.testing.assert_array_equal(np.asarray(new_s["acc"]["scale"],
                                             np.float32),
                                  np.asarray(jnp_s["acc"]["scale"],
                                             np.float32))
    split = {r["name"]: (r["value"], r["leaves"]) for r in sink.records}
    assert len(sink.records) == 2
    assert split == {"frodo.fused_params": (2 * 64 * 256, 1),
                     "frodo.jnp_params": (2 * 2560, 1)}


if hypothesis is not None:
    @hypothesis.given(
        n=st.integers(1, 3000),
        T=st.integers(1, 24),
        cursor=st.integers(0, 1000),
        alpha=st.floats(0.0, 2.0),
        beta=st.floats(0.0, 2.0),
    )
    @hypothesis.settings(
        max_examples=25, deadline=None,
        suppress_health_check=[hypothesis.HealthCheck.function_scoped_fixture])
    def test_exact_kernel_property(n, T, cursor, alpha, beta):
        rng = np.random.default_rng(n * 31 + T)
        g = jnp.asarray(rng.normal(size=n), jnp.float32)
        hist = jnp.asarray(rng.normal(size=(T, n)), jnp.float32)
        w = jnp.asarray(fmem.mu_weights(T, 0.2), jnp.float32)
        c = jnp.int32(cursor % T)
        d1, h1 = ops.frodo_update(g, hist, c, w, alpha, beta)
        d2, h2 = ref.frodo_update_ref(g, hist, c, w, alpha, beta)
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))


def test_kernel_inside_jit_grad_free_update():
    """Kernels compose under jit with the full optimizer loop."""
    from repro.core.frodo import FrodoConfig, apply_updates, frodo
    opt = frodo(FrodoConfig(alpha=0.1, beta=0.02, T=6, lam=0.3,
                            use_kernel=True))
    p = {"w": jnp.ones((130,))}

    @jax.jit
    def step(p, s, g):
        d, s = opt.update(g, s, p)
        return apply_updates(p, d), s

    s = opt.init(p)
    g = {"w": jnp.full((130,), 0.5)}
    for _ in range(3):
        p, s = step(p, s, g)
    assert np.isfinite(np.asarray(p["w"])).all()
