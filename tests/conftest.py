import os

# Tests run on the single real CPU device; the dry-run (and only it) forces
# 512 placeholder devices in its own process.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


@pytest.fixture
def pallas_interpret():
    """Run the Pallas TPU kernels in Pallas' TPU interpret mode for this
    test.  Off the TPU a kernel only runs when a test asks for this."""
    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():
        yield
