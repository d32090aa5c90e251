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


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_expsum_kernel_sweep(shape, dtype):
    rng = np.random.default_rng(hash(("e", shape, str(dtype))) % 2 ** 31)
    K = 6
    g = jnp.asarray(rng.normal(size=shape), dtype)
    acc = jnp.asarray(rng.normal(size=(K,) + shape), jnp.float32)
    rates, coeffs = fmem.fit_expsum(40, 0.15, K)
    rates = jnp.asarray(rates, jnp.float32)
    coeffs = jnp.asarray(coeffs, jnp.float32)
    d1, a1 = ops.frodo_expsum_update(g, acc, rates, coeffs, 0.8, 0.35)
    d2, a2 = ref.frodo_expsum_update_ref(g, acc, rates, coeffs, 0.8, 0.35)
    np.testing.assert_allclose(np.asarray(d1, np.float32),
                               np.asarray(d2, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), rtol=1e-5,
                               atol=1e-5)


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
