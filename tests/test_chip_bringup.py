r"""Bring-up contracts (ISSUE 21): what must hold so that the first
contact with a real chip is never hidden, shared or mis-attributed.

  * no chip -> no verdict: `--backend tpu` on a machine without one
    exits 2 naming the platform — no "Model checking completed", no
    rate line — from the CLI and from a served job alike (the demotion
    that survives, with a host snapshot to resume, is pinned in
    tests/test_chaos.py::test_terminal_device_failure_demotes_with_snapshot);
  * one process per chip: telemetry never initializes a jax backend in
    a process that merely imported jax;
  * the mesh engine's tables are sharded at creation;
  * chip_smoke.py fails without an accelerator and without the
    checkout, and its leg plumbing rehearses on CPU only when asked to,
    saying that it is not a chip run.

All CPU, seconds each (the full rehearsal is marked slow).
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = os.path.join(REPO, "specs")
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(argv, cwd=REPO, timeout=300, **env):
    return subprocess.run(
        argv, cwd=cwd, capture_output=True, text=True, timeout=timeout,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env))


# ------------------------------------------------- no chip, no verdict

def test_backend_tpu_without_a_chip_exits_2_and_prints_no_verdict(
        tmp_path):
    m = str(tmp_path / "m.json")
    r = _run([sys.executable, "-m", "jaxmc", "check",
              os.path.join(SPECS, "constoy.tla"), "--backend", "tpu",
              "--quiet", "--metrics-out", m], JAXMC_DEVICE_RETRIES="0")
    assert r.returncode == 2, (r.stdout, r.stderr[-600:])
    assert "'tpu'" in r.stderr and "error:" in r.stderr
    assert "Model checking completed" not in r.stdout
    assert "states/sec" not in r.stdout and "backend=" not in r.stdout
    assert "falling back" not in r.stderr
    assert not os.path.exists(m)  # no artifact claims a result


def test_served_tpu_job_without_a_chip_fails_with_the_platform_named(
        tmp_path, monkeypatch):
    from jaxmc.serve.daemon import ServeDaemon
    from jaxmc.serve.protocol import ServeClient
    monkeypatch.setenv("JAXMC_DEVICE_RETRIES", "0")
    d = ServeDaemon(str(tmp_path / "spool"), workers=1,
                    quiet=True).start()
    try:
        c = ServeClient("127.0.0.1", d.port)
        code, job = c.submit(os.path.join(SPECS, "constoy.tla"), None,
                             {"backend": "jax", "platform": "tpu"})
        assert code == 200, job
        done = c.wait(job["id"], timeout=120)
        assert done["status"] == "failed", done
        assert "'tpu'" in done["error"]
        assert c.result(job["id"])[0] == 404  # no summary, no counts
    finally:
        d.shutdown()


# -------------------------------------- the preflight oracle (--backend auto)

def test_oracle_answers_inside_its_deadline_and_live_platforms_agree(
        tmp_path):
    """What the deleted `make backend-check` held (ISSUE 43): the oracle
    finds a live platform inside the deadline it was given, a dead
    platform is a named reason and never a failure, and every LIVE
    platform answers the same pinned leg with the same counts (here the
    CPU alone is live; on a machine with a chip the chip joins in)."""
    from jaxmc import obs
    from jaxmc.backend import oracle
    deadline = 60.0      # generous: a loaded box must not decide this
    tel = obs.Telemetry()
    try:
        v = oracle.preflight(deadline_s=deadline, tel=tel,
                             use_cache=False)
    finally:
        oracle.reset_cache_for_tests()
    assert v["platform"] is not None, v["reason"]
    assert v["wall_s"] <= deadline
    assert set(v["probes"]) == {"tpu", "gpu", "cpu"}
    assert v["probes"]["cpu"]["live"] and v["probes"]["cpu"]["devices"] >= 1
    live = [p for p, pr in v["probes"].items() if pr.get("live")]
    for plat, pr in v["probes"].items():
        if plat not in live:
            assert pr.get("error"), (plat, pr)      # a SKIP has a reason
    assert v["platform"] in live
    assert tel.gauges["backend.oracle_choice"] == v["platform"]
    assert tel.gauges["backend.oracle_probe"] == v["probes"]
    counts = {}
    for plat in live:
        m = str(tmp_path / f"{plat}.json")
        # the child pins its own platform; the suite's JAX_PLATFORMS=cpu
        # would override the pin on an accelerator
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        r = subprocess.run(
            [sys.executable, "-m", "jaxmc", "check",
             os.path.join(SPECS, "viewtoy_scaled.tla"),
             "--backend", plat, "--resident", "--no-trace", "--quiet",
             "--max-states", "4000", "--metrics-out", m],
            cwd=REPO, capture_output=True, text=True, timeout=300, env=env)
        assert r.returncode in (0, 3), (plat, r.stderr[-600:])
        art = json.load(open(m))
        assert art["result"]["ok"] and art["result"]["truncated"]
        assert art["env"]["platform"] == plat
        counts[plat] = (art["result"]["generated"],
                        art["result"]["distinct"])
    # max_states is judged a level, so the cut is the same on any target
    # (the spec has a VIEW: which state stands for a view's class is the
    # first in KEY order, so the counts of a cut run are the key function's
    # — 43109 / 5219 under the fingerprint up to ISSUE 51)
    assert set(counts.values()) == {(43252, 5258)}, counts


# ------------------------------------------------ one process per chip

def test_environment_meta_never_initializes_a_backend():
    # the serve daemon stamps job records with environment_meta() and
    # rolls up profiles (device_mem_high_water): with jax merely
    # IMPORTED neither may bring a backend up — on an exclusive
    # accelerator that would make the daemon, not its owner, the chip's
    code = (
        "import sys\n"
        "import jax\n"
        "from jax._src import xla_bridge\n"
        "from jaxmc import obs\n"
        "env = obs.environment_meta()\n"
        "assert env['jax_version'] == jax.__version__, env\n"
        "assert env['platform'] is None and env['device_kind'] is None"
        " and env['device_count'] is None, env\n"
        "assert obs.device_mem_high_water() is None\n"
        "assert obs.Telemetry().summary() is not None\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "jax.devices()\n"
        "env = obs.environment_meta()\n"
        "assert env['platform'] == 'cpu' and env['device_kind'] and "
        "env['device_count'] >= 1, env\n")
    r = _run([sys.executable, "-c", code])
    assert r.returncode == 0, r.stderr[-800:]


# -------------------------------------------- mesh tables at creation

def test_mesh_tables_are_sharded_at_creation():
    import numpy as np
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jaxmc.backend.mesh import MeshExplorer
    from jaxmc.front.cfg import parse_cfg
    from jaxmc.sem.modules import Loader, bind_model
    with open(os.path.join(SPECS, "constoy.cfg")) as fh:
        model = bind_model(
            Loader([SPECS]).load_path(os.path.join(SPECS, "constoy.tla")),
            parse_cfg(fh.read()))
    devs = jax.devices()[:4]
    me = MeshExplorer(model, mesh=Mesh(np.array(devs), ("d",)))
    arr = me._put(np.zeros((4, 64, 3), np.int32))
    assert arr.sharding == NamedSharding(me.mesh, P("d"))
    # every device holds ITS shard only — nothing staged on device 0
    assert sorted((s.device.id, s.data.shape)
                  for s in arr.addressable_shards) == \
        [(d.id, (1, 64, 3)) for d in devs]


# ------------------------------------------------------- chip_smoke.py

def test_chip_smoke_fails_without_an_accelerator(tmp_path):
    r = _run([sys.executable, SMOKE, "--out", str(tmp_path / "out")],
             JAXMC_DEVICE_RETRIES="0")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "FAILED" in r.stderr and "A_ok" in r.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run([sys.executable, "chip_smoke.py"], cwd=str(tmp_path))
    assert r.returncode != 0
    assert r.stdout == ""
    assert os.listdir(tmp_path) == ["chip_smoke.py"]


def _rehearse(tmp_path, legs):
    r = _run([sys.executable, SMOKE, "--rehearse-on-cpu", "--legs", legs,
              "--out", str(tmp_path / "out")], timeout=600,
             # the cache is placed from OUTSIDE, as on the chip; the
             # suite-wide opt-out is lifted so leg B can prove its hits
             JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
             JAXMC_COMPILE_CACHE="on")
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-800:])
    assert "NOT a chip run" in r.stdout.splitlines()[0]
    assert "NOT a chip run" in r.stdout.splitlines()[-1]
    assert '"ok"' not in r.stdout  # a rehearsal prints no result
    return r


def test_chip_smoke_rehearsal_check_legs(tmp_path):
    r = _rehearse(tmp_path, "A,B")
    assert "[A_bad] invariant" in r.stdout and "trace printed" in r.stdout
    # leg B's second process: persistent-cache hits, profile found
    with open(tmp_path / "out" / "B_warm.json") as fh:
        warm = json.load(fh)
    assert warm["counters"]["compile.persistent_cache_hits"] > 0
    assert warm["counters"]["profile.hits"] >= 1
    assert warm["env"]["device_kind"]
    # everything the cache keeps — entries AND profiles — is under the
    # placed directory
    assert os.listdir(tmp_path / "cache" / "profiles")


def test_chip_smoke_rehearsal_symmetry_leg(tmp_path):
    """Leg F (ISSUE 47): cfg SYMMETRY on the resident engine must reduce
    on the device in the sorted form, with the manifest's counts."""
    r = _rehearse(tmp_path, "F")
    assert "[F_sym] symmetry.form=sorted group_order=6" in r.stdout
    assert "[F_sym] counts 2369 generated / 1148 distinct == pin" in r.stdout
    with open(tmp_path / "out" / "F_sym.json") as fh:
        art = json.load(fh)
    assert art["gauges"]["symmetry.form"] == "sorted"
    assert art["counters"]["search.canon_rows"] == 2369


def test_chip_smoke_rehearsal_constraint_leg(tmp_path):
    """Leg G (ISSUE 51): a cfg CONSTRAINT on the resident engine must be
    judged on the device, with the manifest's counts."""
    r = _rehearse(tmp_path, "G")
    assert "[G_con] counts 2587 generated / 1289 distinct == pin" in r.stdout
    assert "[G_con] constraint.compiled=1 rows_discarded=366 " in r.stdout
    with open(tmp_path / "out" / "G_con.json") as fh:
        art = json.load(fh)
    assert art["gauges"]["constraint.compiled"] == 1
    assert art["gauges"]["expand.constraints_interp"] == 0
    assert art["counters"]["search.rows_discarded"] == 366
    assert art["counters"]["search.slots_constrained"] > 0


@pytest.mark.slow
def test_chip_smoke_rehearsal_all_legs(tmp_path):
    r = _rehearse(tmp_path, "A,B,C,D,E,F,G")
    assert "daemon_holds_device=False" in r.stdout
    assert "SIGTERM -> clean drain" in r.stdout
    assert "[E_mesh] counts" in r.stdout
