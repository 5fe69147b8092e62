import hashlib
import os
import random
import sys

# Tests run on the single host device.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

jax.config.update("jax_enable_x64", False)

import numpy as np
import pytest


def pytest_configure(config):
    # Two-tier suite: tier-1 (the pre-commit gate) is `-m "not slow"` and
    # must stay under ~90s on CPU; `slow` holds the large-shape
    # interpret-mode kernel cases and the heavy integration sweeps, run by
    # the dedicated CI job.
    config.addinivalue_line(
        "markers",
        "slow: large-shape / long-running cases excluded from tier-1 "
        "(`pytest -m 'not slow'`); the full tier runs them in CI")
    # chaos: the fault-injection/recovery suite (`pytest -m chaos`), run
    # by the dedicated CI chaos job. Heavy chaos cases carry `slow` too,
    # keeping them out of tier-1; the slow CI job deselects `chaos` so
    # they run exactly once.
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection & recovery cases (`pytest -m chaos`); "
        "heavy ones also carry `slow` to stay out of tier-1")


def _nodeid_seed(nodeid: str) -> int:
    # stable across processes/runs (no PYTHONHASHSEED dependence)
    return int.from_bytes(hashlib.sha1(nodeid.encode()).digest()[:4], "big")


@pytest.fixture(autouse=True)
def _deterministic_seeds(request):
    """Seed the stdlib and numpy PRNGs per test id, so a kernel tolerance
    failure reproduces under any rerun/selection order (`pytest <nodeid>`
    sees the exact arrays the failing full-suite run saw)."""
    seed = _nodeid_seed(request.node.nodeid)
    random.seed(seed)
    np.random.seed(seed)


@pytest.fixture
def rng_key(request):
    """A jax PRNG key derived from the test id — same reproducibility
    contract as _deterministic_seeds for tests that want a jax key."""
    return jax.random.PRNGKey(_nodeid_seed(request.node.nodeid) % (2 ** 31))
