"""Test harness: fake an 8-device TPU pod on CPU.

SURVEY.md §4: the reference de-risks multi-node behavior through a local
single-GPU path with the DISTRIBUTED switch off.  The JAX-native analogue is a
virtual multi-device CPU platform, which lets every data-parallel semantic
(mesh construction, psum gradient sync, sharded batches, LR scaling, resume)
run in CI with no TPU attached.

Both settings go into the environment before the backend initialises, so
the subprocesses the tests start (bench.py, fleet workers, ``ddlt``) run on
the same virtual pod (``utils/virtual_pod.py``); the config update covers a
``jax`` that a pytest plugin imported before this file ran.
"""

import os

# Must precede backend initialization (first jax.devices()/jit call).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_addoption(parser):
    # pyproject.toml sets `timeout` / `timeout_method` for pytest-timeout
    # (per-test deadlines so a hang in watchdog/prefetch/scheduler threading
    # fails loudly).  When the plugin is not installed, declare the same ini
    # keys as inert placeholders so the options don't raise unknown-key
    # warnings — the suite then simply runs without per-test deadlines.
    try:
        import pytest_timeout  # noqa: F401
    except ImportError:
        parser.addini("timeout", "per-test deadline (pytest-timeout absent: inert)")
        parser.addini("timeout_method", "pytest-timeout method (inert)")

# --- two-tier suite -------------------------------------------------------
# tests/slow_tests.txt lists test IDs (relative to tests/, parametrized IDs
# cover every param) measured over ~5 s on a single core; conftest marks
# them ``slow`` at collection so ``make test-fast`` (-m "not slow") stays
# under its CI budget.  Regenerate after perf-relevant changes with:
#   python -m pytest tests/ -q --durations=80   (then paste calls >5 s)
_SLOW_MANIFEST = os.path.join(os.path.dirname(__file__), "slow_tests.txt")


def _slow_ids():
    try:
        with open(_SLOW_MANIFEST) as f:
            return {ln.strip() for ln in f if ln.strip() and not ln.startswith("#")}
    except OSError:
        return None


def pytest_collection_modifyitems(config, items):
    slow = _slow_ids()
    if slow is None:
        # Without the manifest the "fast" tier silently becomes the full
        # ~45-minute suite; make the degradation loud.
        import warnings

        warnings.warn(
            f"slow-test manifest {_SLOW_MANIFEST} missing — no slow marks "
            "applied, -m 'not slow' will run (almost) everything",
            stacklevel=1,
        )
        return
    if not slow:
        return
    for item in items:
        # item.nodeid is "tests/test_x.py::test_y[param]"; the manifest
        # stores it without the tests/ prefix and without param brackets so
        # one line covers every parametrization.
        nodeid = item.nodeid
        if nodeid.startswith("tests/"):
            nodeid = nodeid[len("tests/"):]
        base = nodeid.split("[", 1)[0]
        if nodeid in slow or base in slow:
            item.add_marker(pytest.mark.slow)


@pytest.fixture
def tmp_env(tmp_path):
    """A throwaway .env path."""
    return tmp_path / ".env"
