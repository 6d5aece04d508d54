# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# single real CPU device; only launch/dryrun.py (separate process) forces 512.
import numpy as np
import pytest

import jax


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture
def cold_fleet_cache():
    """Empty the fleet driver's cross-call program cache first, so that the
    test's first ``run_fleet`` call compiles as in a fresh process."""
    from repro.fl import driver
    driver.clear_chunk_cache()
