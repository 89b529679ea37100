import os
import sys

# Tests run on CPU with a virtual 8-device mesh so multi-chip sharding logic
# is exercised without TPU hardware (the driver separately dry-runs
# multichip). jax.config.update pins the platform inside this process
# whatever JAX_PLATFORMS says; subprocesses the tests start inherit the
# environment, which the tier-1 command sets to JAX_PLATFORMS=cpu.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")

# Every device-backend run uses the persistent compile cache
# (jaxmc/compile/cache.py) — the suite opts OUT: XLA:CPU persists
# AOT-compiled blobs whose reload can hang when the cache was written by
# a different machine/build (observed: cache hit on the resident-mode
# while_loop program never returns), and every reload logs a screen of
# machine-feature warnings.  Tests that exercise the cache opt back in
# against a tmp dir.
os.environ.setdefault("JAXMC_COMPILE_CACHE", "off")

try:
    import jax
    jax.config.update("jax_platforms", "cpu")
except ImportError:
    pass

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# ISSUE 17: every metrics write appends a trajectory point to the run
# ledger (~/.cache/jaxmc/ledger.jsonl) unless redirected — the suite
# must never pollute the developer's real history.  Tests that need a
# live ledger monkeypatch JAXMC_LEDGER to a tmp path themselves.
os.environ.setdefault("JAXMC_LEDGER", "off")

REFERENCE = os.environ.get("JAXMC_REFERENCE", "/root/reference")

# The reference spec corpus is mounted in the DRIVER environment only —
# builder/CI containers run without it (ISSUE 6 satellite).  Tests that
# load reference specs skip with this named marker instead of failing,
# so tier-1 is green wherever the repo is checked out.
HAVE_REFERENCE = os.path.isdir(os.path.join(REFERENCE, "examples"))

import pytest  # noqa: E402

needs_reference = pytest.mark.skipif(
    not HAVE_REFERENCE,
    reason=f"needs the reference spec corpus at {REFERENCE} (driver "
           f"environment only; point JAXMC_REFERENCE at a checkout)")


@pytest.fixture(autouse=True)
def _forget_programs():
    """The program registry (ISSUE 37, jaxmc/compile/cache.py) is state
    of the PROCESS, and a worker runs many tests in one: a test that
    counts its engine's compiles, or patches what a trace reads behind
    the signature's back, must not be handed the program an earlier
    test's engine made.  Every test starts with an empty registry;
    tests/test_program_registry.py keeps its own warm."""
    try:
        from jaxmc.compile.cache import forget_programs
    except ImportError:
        yield
        return
    forget_programs()
    yield
