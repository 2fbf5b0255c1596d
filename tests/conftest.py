"""Shared fixtures.  NOTE: no XLA_FLAGS here — unit/smoke tests must see
the single real CPU device; only the dry-run subprocess tests force fake
device counts (in their own subprocess env)."""

import jax
import pytest

from repro.dist.sharding import Sharder


@pytest.fixture(scope="session")
def nosharder() -> Sharder:
    return Sharder(None, {})


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
