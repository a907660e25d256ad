"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches must
see 1 device (multi-device tests set their own flag in a fresh process).
"""
import numpy as np
import pytest
from hypothesis import settings as _h_settings

# Flaky-seed hygiene: property tests must reproduce locally from a CI log.
_h_settings.register_profile(
    "repro-derandomize", _h_settings(derandomize=True, deadline=None))
_h_settings.load_profile("repro-derandomize")


def pytest_addoption(parser):
    parser.addoption(
        "--delta-seed",
        action="store",
        type=int,
        default=0,
        help="Extra seed mixed into the graph-delta mutation suites "
             "(tests/test_graph_delta.py). CI failures print the active "
             "seed; rerun with `--delta-seed=<n>` to reproduce locally.",
    )


@pytest.fixture
def delta_seed(request) -> int:
    """The --delta-seed CLI value (0 by default, pinned in CI)."""
    return int(request.config.getoption("--delta-seed"))


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def random_rotation(rng) -> np.ndarray:
    a = np.linalg.qr(rng.standard_normal((3, 3)))[0]
    if np.linalg.det(a) < 0:
        a[:, 0] *= -1
    return a.astype(np.float32)
