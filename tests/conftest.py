"""Shared test utilities.

No ``hypothesis`` offline — ``sweep_cases`` provides seeded random shape
sweeps with the same spirit: each property test runs across a randomized
family of shapes/dtypes and any failure prints the exact case for replay.
"""

import numpy as np
import pytest


def sweep_cases(seed: int, n: int, gen):
    """Deterministic pseudo-random case list: gen(rng) -> case dict."""
    rng = np.random.default_rng(seed)
    return [gen(rng) for _ in range(n)]


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")


@pytest.fixture(autouse=True, scope="module")
def _drop_compiled_programs():
    """Free each module's compiled programs when it ends: a profile keeps
    the HLO of every live program in the process, so a later module that
    reads its own program from a profile would otherwise see this one's."""
    yield
    import jax

    jax.clear_caches()
