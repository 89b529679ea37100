r"""The persistent compile cache: one resolver, one guarded enabler
(jaxmc/compile/cache.py).

Two contracts under test.  PLACEMENT: the cache is where
JAX_COMPILATION_CACHE_DIR says (jaxmc then never writes the directory
knob and never moves the directory), else `<checkout>/.jax_cache`; no
temp dir, pid or clock value in any cache or profile path; capacity
profiles live inside the resolved directory.  SAFETY: a cache problem —
wedged blob reload, corrupt entry, foreign build — must NEVER wedge or
fail a run: every guard defect degrades to cold compilation (enable
returns None, the run proceeds uncached), and the good path proves
cross-process cache hits in `compile.persistent_cache_hits`.
Fault sites: cache_hang / cache_corrupt (jaxmc/faults.py).
"""

import json
import os
import subprocess
import sys

import pytest

from jaxmc import faults, obs
from jaxmc.compile import cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_checkout_cache_dir = cache.checkout_cache_dir  # the fixture patches it


@pytest.fixture(autouse=True)
def _fresh(monkeypatch, tmp_path):
    """Every test gets an isolated cache dir standing in for the
    checkout's (the unset-environment path, where jaxmc sets the
    directory knob itself), a clean fault registry, and the suite-wide
    opt-out lifted."""
    monkeypatch.delenv("JAXMC_FAULTS", raising=False)
    monkeypatch.delenv("JAXMC_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAXMC_PROFILE_STORE", raising=False)
    monkeypatch.setenv("JAXMC_CACHE_PROBE", "0")  # probe-needing tests
    # opt back in explicitly — jax-import subprocesses are expensive
    monkeypatch.setattr(cache, "checkout_cache_dir",
                        lambda: str(tmp_path / "xla_cache"))
    faults.reset_for_tests()
    yield
    faults.reset_for_tests()
    # leave no tmp-dir cache configured for the rest of the session
    import jax
    from jax._src import compilation_cache
    jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _dir(tmp_path):
    return str(tmp_path / "xla_cache")


# ------------------------------------------------------------ placement

def test_unset_env_resolves_inside_the_checkout(tmp_path):
    assert _checkout_cache_dir() == os.path.join(REPO, ".jax_cache")
    assert cache.resolve_cache_dir() == _dir(tmp_path)
    assert cache.profile_dir() == os.path.join(_dir(tmp_path),
                                               "profiles")


def test_env_places_cache_and_profiles(monkeypatch, tmp_path):
    d = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    assert cache.resolve_cache_dir() == d
    assert cache.profile_dir() == os.path.join(d, "profiles")
    assert cache.profile_path("M", "0" * 16).startswith(d + os.sep)


def test_no_temp_pid_or_clock_in_cache_module_paths():
    # the path is part of what makes a second process hit: nothing in
    # the module may derive one from a temp dir, a pid or the clock
    src = open(cache.__file__.replace(".pyc", ".py")).read()
    assert "tempfile" not in src and "gettempdir" not in src
    assert "getpid" not in src
    for line in src.splitlines():
        if "time()" in line:  # clock reads compare ages, never name files
            assert "join(" not in line and 'f"' not in line, line


def test_env_set_path_never_writes_the_directory_knob(tmp_path):
    # with JAX_COMPILATION_CACHE_DIR set, jax reads the directory on
    # its own: jaxmc must leave the knob alone (and equal to the env
    # value), keep every artifact under that dir, and still hit
    d = str(tmp_path / "placed")
    code = (
        "import os, sys, json\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "writes = []\n"
        "_upd = jax.config.update\n"
        "def spy(k, v):\n"
        "    writes.append(k)\n"
        "    return _upd(k, v)\n"
        "jax.config.update = spy\n"
        "from jaxmc import obs\n"
        "from jaxmc.compile import cache\n"
        "tel = obs.Telemetry()\n"
        f"assert cache.enable_guarded_cache(tel=tel) == {d!r}\n"
        "assert 'jax_compilation_cache_dir' not in writes, writes\n"
        f"assert jax.config.jax_compilation_cache_dir == {d!r}\n"
        "import jax.numpy as jnp\n"
        "with obs.use(tel):\n"
        "    jax.jit(lambda x: x * 3 + 7)(jnp.arange(5))"
        ".block_until_ready()\n"
        "print('HITS', tel.counters.get("
        "'compile.persistent_cache_hits', 0))\n")
    outs = []
    for _ in range(2):
        p = subprocess.run(
            [sys.executable, "-c", code], capture_output=True,
            text=True, timeout=240,
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     JAX_COMPILATION_CACHE_DIR=d,
                     JAXMC_COMPILE_CACHE="on", JAXMC_CACHE_PROBE="0"))
        assert p.returncode == 0, p.stderr[-800:]
        outs.append(int(p.stdout.split("HITS")[1].strip()))
    assert outs[0] == 0, "first process must compile cold"
    assert outs[1] > 0, "second process must hit the persistent cache"
    assert sorted(os.listdir(tmp_path)) == ["placed"]


# --------------------------------------------------------------- safety

def test_guard_enables_and_fingerprints(tmp_path):
    tel = obs.Telemetry()
    d = cache.enable_guarded_cache(tel=tel)
    assert d == _dir(tmp_path)
    # the build-fingerprint sentinel exists and matches this build
    meta = json.load(open(os.path.join(d, "jaxmc.cache.meta.json")))
    assert meta["python"] and meta["jax"]
    assert tel.gauges["compile.persistent_cache_guard"].startswith("ok")
    import jax
    assert jax.config.jax_compilation_cache_dir == d


def test_env_opt_out_disables(monkeypatch, tmp_path):
    monkeypatch.setenv("JAXMC_COMPILE_CACHE", "off")
    tel = obs.Telemetry()
    assert cache.enable_guarded_cache(tel=tel) is None
    assert tel.gauges["compile.persistent_cache_guard"].startswith(
        "disabled")
    assert not os.path.exists(_dir(tmp_path))


@pytest.mark.chaos
def test_hang_fault_falls_back_cold(monkeypatch, tmp_path):
    # the known failure class: a blob reload that never returns. The
    # probe child wedges (cache_hang), OUR timeout fires and the caller
    # gets the cold path — never a hang, and the dir stays where it is
    monkeypatch.setenv("JAXMC_CACHE_PROBE", "1")
    monkeypatch.setenv("JAXMC_FAULTS", "cache_hang")
    faults.reset_for_tests()
    tel = obs.Telemetry()
    assert cache.enable_guarded_cache(tel=tel, timeout_s=6) is None
    g = tel.gauges["compile.persistent_cache_guard"]
    assert g.startswith("cold-fallback:") and "probe" in g
    assert tel.counters["compile.persistent_cache_fallbacks"] == 1
    assert os.listdir(tmp_path) == ["xla_cache"]
    # the run is intact: a compile still works, just uncached
    import jax
    import jax.numpy as jnp
    assert not jax.config.jax_enable_compilation_cache
    assert int(jax.jit(lambda x: x + 1)(jnp.int32(1))) == 2


@pytest.mark.chaos
def test_corrupt_entry_quarantined_cache_continues(monkeypatch,
                                                   tmp_path):
    # one corrupt entry must never disable the whole cache: the scan
    # quarantines it into <dir>/.quarantine and the cache enables
    d = _dir(tmp_path)
    os.makedirs(d)
    with open(os.path.join(d, "jit_f-deadbeef-cache"), "wb") as fh:
        fh.write(b"x" * 64)
    monkeypatch.setenv("JAXMC_FAULTS", "cache_corrupt")
    faults.reset_for_tests()
    tel = obs.Telemetry()
    assert cache.enable_guarded_cache(tel=tel) == d
    assert tel.counters["compile.persistent_cache_quarantines"] >= 1
    assert os.listdir(os.path.join(d, ".quarantine")) == \
        ["jit_f-deadbeef-cache"]
    assert "quarantined 1 corrupt entry" in \
        tel.gauges["compile.persistent_cache_guard"]


def test_foreign_build_fingerprint_compiles_cold_and_says_so(tmp_path):
    # a cache written by another build is exactly the reload-hang class:
    # this process compiles cold BEFORE jax ever reads a blob, names the
    # reason, and leaves the directory exactly as it found it
    d = _dir(tmp_path)
    os.makedirs(d)
    foreign = {"python": "0.0.0", "jax": "0.0.0", "machine": "vax"}
    with open(os.path.join(d, "jaxmc.cache.meta.json"), "w") as fh:
        json.dump(foreign, fh)
    with open(os.path.join(d, "jit_old-cache"), "wb") as fh:
        fh.write(b"foreign blob")
    tel = obs.Telemetry()
    assert cache.enable_guarded_cache(tel=tel) is None
    g = tel.gauges["compile.persistent_cache_guard"]
    assert g.startswith("cold-fallback:") and "another build" in g
    assert os.listdir(tmp_path) == ["xla_cache"]
    assert sorted(os.listdir(d)) == ["jaxmc.cache.meta.json",
                                     "jit_old-cache"]
    assert json.load(open(os.path.join(
        d, "jaxmc.cache.meta.json"))) == foreign
    import jax
    assert not jax.config.jax_enable_compilation_cache
