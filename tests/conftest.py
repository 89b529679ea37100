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


# A deliberately SLOW interp job for the serve / fleet / drain tests
# (moved here from the deleted trace-check harness, ISSUE 43): ~230
# distinct states over 21 levels at bound=20, a frontier wide enough
# (> workers*4) that the interp fork pool really forks, a CONSTRAINT tight
# enough that the analyze interval fixpoint converges BEFORE widening and
# proves an estimate (so `search.progress_est` exists); the \A guard costs
# ~q interpreter steps a successor, which is what makes the search last
# seconds instead of milliseconds.
SLOW_SPEC = """\
-------------------------- MODULE traceload --------------------------
EXTENDS Naturals

VARIABLES a, b

Slow == \\A i \\in 1 .. {q} : i + a >= 0

Init == a = 0 /\\ b = 0

Next == \\/ a' = a + 1 /\\ b' = b /\\ Slow
        \\/ b' = b + 1 /\\ a' = a /\\ Slow

Bound == a + b <= {bound}

TypeInv == a >= 0 /\\ b >= 0

Spec == Init /\\ [][Next]_<<a, b>>
======================================================================
"""

SLOW_CFG = """\
SPECIFICATION Spec
CONSTRAINT Bound
INVARIANT TypeInv
CHECK_DEADLOCK FALSE
"""


def write_slow_spec(spec_dir, name, q, bound):
    """`<spec_dir>/<name>.tla` + `.cfg` of the slow job; returns the spec."""
    os.makedirs(str(spec_dir), exist_ok=True)
    spec = os.path.join(str(spec_dir), f"{name}.tla")
    with open(spec, "w", encoding="utf-8") as fh:
        fh.write(SLOW_SPEC.format(q=q, bound=bound)
                 .replace("MODULE traceload", f"MODULE {name}"))
    with open(os.path.join(str(spec_dir), f"{name}.cfg"), "w",
              encoding="utf-8") as fh:
        fh.write(SLOW_CFG)
    return spec


def timeline_counts(traces):
    """`obs timeline --fail-on-orphans` over trace files: (exit code, the
    counts of its machine-parseable `summary:` line, the whole output)."""
    import io
    from jaxmc.obs.report import main as obs_main
    buf = io.StringIO()
    rc = obs_main(["timeline", "--fail-on-orphans"] + list(traces), out=buf)
    out = buf.getvalue()
    summary = [ln for ln in out.splitlines()
               if ln.startswith("summary: ")][-1]
    return rc, {k: int(v) for k, v in
                (kv.split("=") for kv in
                 summary[len("summary: "):].split())}, out


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
