"""Shared fixtures. NOTE: no XLA_FLAGS here on purpose — tests must see
the plain 1-device CPU; only launch/dryrun.py forces 512 devices."""

import jax
import numpy as np
import pytest


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _hermetic_plan_cache(tmp_path_factory, monkeypatch):
    """Point the rosa.compile plan cache at a session-private directory so
    tests never read a stale plan from (or write into) the user's real
    ~/.cache — cache-behaviour tests pass their own `cache=` explicitly."""
    monkeypatch.setenv(
        "ROSA_PLAN_CACHE",
        str(tmp_path_factory.getbasetemp() / "rosa-plan-cache"))


# ---------------------------------------------------------------------------
# Opt-in NaN/Inf guard for the analog numerics path
# ---------------------------------------------------------------------------
def pytest_addoption(parser):
    parser.addoption(
        "--nan-guard", action="store_true", default=False,
        help="run @analog_guard tests under jax_debug_nans/jax_debug_infs "
             "(any NaN/Inf in the analog path raises at the producing op)")


@pytest.fixture(autouse=True)
def _nan_guard(request):
    """For tests marked `analog_guard` under --nan-guard: every op that
    produces a NaN or Inf raises immediately, turning a silent numerics
    regression in the MRR transfer / OSA accumulation path into a
    pinpointed failure.  Off by default — the debug checks force re-traces
    and would slow the whole suite."""
    if request.node.get_closest_marker("analog_guard") is None \
            or not request.config.getoption("--nan-guard"):
        yield
        return
    with jax.debug_nans(True), jax.debug_infs(True):
        yield


# ---------------------------------------------------------------------------
# Comparing the same float32 computation across two compiled programs
# ---------------------------------------------------------------------------
F32_EPS = float(np.finfo(np.float32).eps)
# the largest separation observed on the installed XLA is ~10.4 ULPs, for
# 2-layer transformer logits after several decode steps (tests/test_serve.py)
CROSS_PROGRAM_ULPS = 16


def assert_cross_program_close(actual, desired,
                               ulps: int = CROSS_PROGRAM_ULPS) -> None:
    """`actual` and `desired` are the same float32 values computed by two
    differently compiled programs (jit vs eager, a batch-N step vs the
    batch-1 oracle, chunked vs whole prefill).  XLA fuses and orders
    float ops per program, so the two agree to within `ulps` float32 ULPs
    of the array's largest magnitude, not bitwise.  Comparisons within ONE
    compiled program (row independence, slot invariance) stay bitwise."""
    a = np.asarray(actual, np.float64)
    d = np.asarray(desired, np.float64)
    assert a.shape == d.shape, (a.shape, d.shape)
    scale = max(float(np.max(np.abs(d), initial=0.0)), 1e-30)
    err = float(np.max(np.abs(a - d), initial=0.0))
    assert err <= ulps * F32_EPS * scale, (
        f"max |diff| {err:.3e} is {err / (F32_EPS * scale):.1f} float32 "
        f"ULPs of the largest magnitude {scale:.3e}; bound is {ulps}")
