"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's `fakedist` logic-test configs
(pkg/sql/logictest/logictestbase/logictestbase.go:270-460), which
simulate multi-node distribution in one process via a fake span
resolver — here, XLA's host-platform device-count flag gives us 8
virtual devices so every sharding/collective path compiles and runs
without TPU hardware.
"""

import os

# expensive structural invariant checks are on for the whole suite
# (the reference's CrdbTestBuild assertions; utils/invariants.py)
os.environ.setdefault("COCKROACH_TPU_INVARIANTS", "1")

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
)
os.environ.setdefault("JAX_ENABLE_X64", "1")

import tempfile

# Hermetic cold-start state: the engine keeps its persistent XLA
# compile cache + shapes journal where
# JAX_COMPILATION_CACHE_DIR says (exec/coldstart.py), and JAX reads
# that variable at import — so point it at a throwaway session dir
# BEFORE jax is imported. One dir for the whole run lets later tests
# deserialize XLA programs earlier tests already compiled, which is
# what keeps the tier-1 wall clock inside its budget.
_SESSION_CACHE = tempfile.mkdtemp(prefix="cockroach-tpu-test-cache-")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _SESSION_CACHE

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs with -m 'not slow'; register the marker so the
    # deselection is declared, not a typo (PytestUnknownMarkWarning)
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1")
    config.addinivalue_line(
        "markers", "graftlint: static-analysis gate tests "
        "(python -m cockroach_tpu.analysis); select with -m graftlint")


@pytest.fixture(autouse=True)
def _hermetic_coldstart():
    """Nothing may leak into the checkout's default cache directory:
    on-disk cold-start state stays opt-in for tests."""
    from cockroach_tpu.exec import coldstart
    default_dir = coldstart.checkout_cache_dir()
    existed_before = os.path.exists(default_dir)
    yield
    assert existed_before or not os.path.exists(default_dir), (
        "persistent compile cache escaped the test tmpdir into "
        + default_dir)


@pytest.fixture
def private_compile_cache(tmp_path, monkeypatch):
    """A cold cache directory of this test's own (cache-miss
    assertions, journals other tests must not crowd). Places it the
    way a launcher does — through JAX_COMPILATION_CACHE_DIR — and,
    because jax is already imported here, repeats what jax does with
    that variable at import."""
    from jax.experimental.compilation_cache import compilation_cache
    d = str(tmp_path / "compile-cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    jax.config.update("jax_compilation_cache_dir", d)
    compilation_cache.reset_cache()
    yield d
    jax.config.update("jax_compilation_cache_dir", _SESSION_CACHE)
    compilation_cache.reset_cache()
