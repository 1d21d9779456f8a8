import os

# Tests see the real single CPU device by default; individual tests that need
# a small multi-device mesh spawn with XLA_FLAGS via the sharded fixtures
# below (which require this env var to be set BEFORE jax initializes, so we
# set a modest 8 here -- small enough not to slow single-device tests).
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import numpy as np
import pytest

from repro.core.distmatrix import DistContext, make_context
from jax.sharding import Mesh


@pytest.fixture
def host_resident(monkeypatch):
    """Count 1 GiB free on a device that reports no memory stats (the CPU),
    so a store keeps a queried artifact on it as it does on a chip."""
    import repro.store.embstore as embstore

    monkeypatch.setattr(embstore, "NO_STATS_FREE_BYTES", 2**30)


@pytest.fixture(scope="session")
def ctx1() -> DistContext:
    """1x1 mesh context."""
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    return make_context(Mesh(dev, ("data", "model")))


@pytest.fixture(scope="session")
def ctx22() -> DistContext:
    """2x2 mesh context (4 fake CPU devices)."""
    dev = np.array(jax.devices()[:4]).reshape(2, 2)
    return make_context(Mesh(dev, ("data", "model")))


@pytest.fixture(scope="session")
def mesh22() -> Mesh:
    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))


@pytest.fixture(scope="session")
def mesh_pod() -> Mesh:
    """(2, 2, 2) pod/data/model mesh -- multi-pod code paths."""
    return Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2), ("pod", "data", "model"))
