r"""The served path under commit-replay traffic (ISSUE 36), at toy size on
XLA:CPU: ONE real daemon process with its device-owner child, two runners
(tenants `ci-a`, `ci-b`), each a closed loop through `edit, edit, edit,
re-run` with a suite of two cfgs a commit (Procs 2 / MaxMoney 3 and Procs 3 /
MaxMoney 2 of `bench/specs/transfer_scaled.tla`), resident engine, the
daemon's own persistent compile cache (a tmp dir: the first job of a cfg
compiles, every later engine loads).

Held here, one parametrised case per job so that each counts:
  - every verdict equals the exact interpreter's AND the benchmark's plain
    reference's (`bench/reference/transfer_scaled.py`);
  - an edit is answered cold (`warm_engine` false, not resumed, a finalized
    checkpoint written), a re-run by the warm engine from that checkpoint
    with the same counts — the replay over the checkpoint's rows as heads
    (`bfs._run_resident`, ISSUE 35);
  - the record's clock (`submitted_at`, `started_at`, `finished_at`) is in
    order inside the client's own wall and holds the owner's `job_wall_s`;
  - the owner did not die, nothing was refused, nothing is left running;
  - a verdict with one count changed fails the benchmark's comparison.
"""

import functools
import importlib.util
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

import pytest

from jaxmc.engine.explore import Explorer
from jaxmc.serve.protocol import ServeClient
from jaxmc.session import load_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench")
SPEC = os.path.join(BENCH, "specs", "transfer_scaled.tla")
SUITE = {
    "2p3": "SPECIFICATION Spec\nINVARIANT AliceBounded\nCONSTANTS\n"
           "  Procs = {p1, p2}\n  MaxMoney = 3\n",
    "3p2": "SPECIFICATION Spec\nINVARIANT AliceBounded\nCONSTANTS\n"
           "  Procs = {p1, p2, p3}\n  MaxMoney = 2\n",
}
RUNNERS = ("ci-a", "ci-b")
CYCLE = ("edit", "edit", "edit", "rerun")
OPTS = {"backend": "jax", "platform": "cpu", "resident": True,
        "no_trace": True}
#: every job of the window: (runner, step of the cycle, cfg of the suite)
JOBS = [(r, i, c) for r in RUNNERS for i in range(len(CYCLE))
        for c in SUITE]


@functools.lru_cache(maxsize=None)
def _bench(name):
    """A module of bench/ by file (bench/ is no package; `lib` is imported
    by the others under that name)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    path = os.path.join(BENCH, name)
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(name)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _runner(client, tenant, work, cfgs, out, errors):
    """One CI runner: a closed loop, one commit in flight, one cycle."""
    lib = _bench("lib.py")
    text = open(SPEC, encoding="utf-8").read()
    try:
        k, spec_path = 0, None
        for step, kind in enumerate(CYCLE):
            if kind == "edit":
                k += 1
                d = os.path.join(work, "commits", f"{tenant}-{k}")
                os.makedirs(d)
                spec_path = os.path.join(d, os.path.basename(SPEC))
                with open(spec_path, "w", encoding="utf-8") as fh:
                    fh.write(lib.stamp_spec(text, f"{tenant} commit {k}"))
            inflight = []
            for label, cfg_path in cfgs.items():
                job = {"runner": tenant, "step": step, "kind": kind,
                       "label": label, "t_post": time.time()}
                code, body = client.submit(spec_path, cfg_path, OPTS,
                                           tenant=tenant)
                assert code == 200, (code, body)
                job.update(id=body["id"], sig=body["sig"])
                inflight.append(job)
            deadline = time.time() + 180
            while inflight:
                assert time.time() < deadline, "no verdict in 180 s"
                for job in list(inflight):
                    code, rec = client.job(job["id"])
                    if code == 200 and rec.get("status") in (
                            "done", "failed", "drained", "quarantined"):
                        job.update(t_seen=time.time(), rec=rec)
                        code, art = client.result(job["id"])
                        job.update(t_result=time.time(),
                                   art=art if code == 200 else None)
                        inflight.remove(job)
                        out[(tenant, step, job["label"])] = job
                if inflight:
                    time.sleep(0.05)
    except BaseException as ex:  # noqa: BLE001 — re-raised by the fixture
        errors.append(ex)


@pytest.fixture(scope="module")
def stream(tmp_path_factory):
    work = str(tmp_path_factory.mktemp("stream"))
    spool, cache = os.path.join(work, "spool"), os.path.join(work, "cache")
    cfgs = {}
    for label, text in SUITE.items():
        cfgs[label] = os.path.join(work, label + ".cfg")
        with open(cfgs[label], "w", encoding="utf-8") as fh:
            fh.write(text)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAXMC_LEDGER="off", JAX_COMPILATION_CACHE_DIR=cache,
               JAXMC_PROFILE_STORE=os.path.join(work, "profiles"))
    env.pop("JAXMC_COMPILE_CACHE", None)   # the daemon's own cache, on
    err = open(os.path.join(work, "daemon.err"), "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "jaxmc.serve", "run", "--spool", spool,
         "--workers", "2", "--quiet"], cwd=REPO, env=env,
        stdout=subprocess.DEVNULL, stderr=err)
    jobs, errors, status, owner_pid = {}, [], {}, None
    try:
        stamp, deadline = os.path.join(spool, "serve.json"), time.time() + 60
        client = None
        while client is None:
            assert proc.poll() is None and time.time() < deadline
            try:
                info = json.load(open(stamp))
                if info.get("status") == "serving" and \
                        info.get("pid") == proc.pid:
                    client = ServeClient(info["host"], info["port"])
            except (OSError, ValueError):
                time.sleep(0.05)
        threads = [threading.Thread(target=_runner, args=(
            client, t, work, cfgs, jobs, errors)) for t in RUNNERS]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        code, status = client.status()
        assert code == 200
        owner_pid = status.get("device_owner_pid")
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            rc = proc.wait()
        err.close()
    if errors:
        raise errors[0]
    return {"jobs": jobs, "status": status, "rc": rc, "cfgs": cfgs,
            "owner_pid": owner_pid, "work": work}


@pytest.fixture(scope="module")
def answers():
    """{cfg label: (the exact interpreter's result, the plain
    reference's)} — two yardsticks that share no code."""
    ref = _bench(os.path.join("reference", "transfer_scaled.py"))
    out = {}
    for label, text in SUITE.items():
        n, m, invs = ref.parse_cfg(text)
        assert invs
        with tempfile.NamedTemporaryFile("w", suffix=".cfg") as fh:
            fh.write(text)
            fh.flush()
            exact = Explorer(load_model(SPEC, fh.name, False)).run()
        out[label] = (exact, ref.explore(n, m))
    return out


def _ids(job):
    return f"{job[0]}-{CYCLE[job[1]]}{job[1]}-{job[2]}"


@pytest.mark.parametrize("key", JOBS, ids=_ids)
def test_verdict_equals_interpreter_and_reference(stream, answers, key):
    job = stream["jobs"][key]
    assert job["rec"]["status"] == "done" and job["art"], job["rec"]
    res = job["art"]["result"]
    exact, plain = answers[key[2]]
    assert (res["generated"], res["distinct"], res["diameter"]) == \
        (exact.generated, exact.distinct, exact.diameter)
    assert (res["generated"], res["distinct"], res["diameter"]) == \
        (plain["generated"], plain["distinct"], plain["diameter"])
    assert res["ok"] is True and exact.ok and plain["ok"]
    assert res["truncated"] is False and res["finished_on"] == "jax"
    # the benchmark's own comparison says the same
    assert _bench("lib.py").compare(res, plain, _ids(key))


@pytest.mark.parametrize("key", JOBS, ids=_ids)
def test_edit_is_cold_and_rerun_replays_warm(stream, key):
    job = stream["jobs"][key]
    art, sv = job["art"], job["art"]["serve"]
    rerun = job["kind"] == "rerun"
    assert sv["device_owner"] is True
    assert bool(sv["warm_engine"]) is rerun
    assert bool(sv["resumed_from_checkpoint"]) is rerun
    phases = {p["name"] for p in art["phases"]}
    if rerun:
        # the previous edit's jobs, byte for byte: same signature, same
        # counts, no engine built, no dispatch of the search program, no
        # second checkpoint (the replay's source IS its checkpoint path)
        prev = stream["jobs"][(key[0], key[1] - 1, key[2])]
        assert job["sig"] == prev["sig"]
        assert art["result"]["generated"] == \
            prev["art"]["result"]["generated"]
        assert art["result"]["distinct"] == prev["art"]["result"]["distinct"]
        assert art["result"]["diameter"] == prev["art"]["result"]["diameter"]
        assert "engine_build" not in phases
        assert "bfs.resident_run" not in art["prof"]["sites"]
        assert sv["window_recompiles"] == 0
        assert "checkpoint.write" not in phases
    else:
        sigs = [j["sig"] for k, j in stream["jobs"].items()
                if j["kind"] == "edit" and k != key]
        assert job["sig"] not in sigs          # a new content hash each
        assert {"load", "parse", "engine_build", "search",
                "checkpoint.write"} <= phases
        assert art["prof"]["sites"]["bfs.resident_run"]["dispatches"] >= 1


@pytest.mark.parametrize("key", JOBS, ids=_ids)
def test_record_clock_inside_the_clients_wall(stream, key):
    job = stream["jobs"][key]
    rec, sv = job["rec"], job["art"]["serve"]
    times = [job["t_post"], rec["submitted_at"], rec["started_at"],
             rec["finished_at"], job["t_seen"], job["t_result"]]
    assert times == sorted(times), times
    # the run itself lies inside started -> finished (the owner's pipe,
    # the artifact's and the record's hard writes are the rest)
    assert sv["job_wall_s"] <= rec["finished_at"] - rec["started_at"] + 0.01
    # the record accounts for the client's wall: what lies outside
    # submitted -> finished is the POST's way in (admission, lint,
    # signature), at most one poll and the result's GET
    client = job["t_result"] - job["t_post"]
    inside = rec["finished_at"] - rec["submitted_at"]
    assert 0.0 <= client - inside < 0.5 + 0.05 * client, (client, inside)


def test_window_holds_whole_cycles(stream):
    jobs = stream["jobs"]
    assert sorted(jobs) == sorted(JOBS)
    kinds = [j["kind"] for j in jobs.values()]
    assert kinds.count("edit") == 12 and kinds.count("rerun") == 4
    assert len({j["id"] for j in jobs.values()}) == 16


def test_owner_survived_and_nothing_was_refused(stream):
    st, counters = stream["status"], stream["status"]["counters"]
    assert st["daemon_holds_device"] is False
    assert st["device_owner_pid"]
    assert counters.get("serve.owner_respawns", 0) == 0
    assert counters.get("serve.admission_rejected", 0) == 0
    assert st["quarantined"] == 0 and st["jobs_failed"] == 0
    assert counters["serve.jobs_done"] == 16
    assert counters["serve.warm_hits"] == 4
    assert counters["serve.cold_runs"] == 12


def _searched(res):
    return (res["generated"], res["distinct"], res["diameter"])


@pytest.mark.parametrize("label", sorted(SUITE))
def test_later_engines_dispatch_the_program_the_owner_holds(stream, label):
    """A new signature builds a new engine, and since ISSUE 37 an edit
    that left the model unchanged no longer asks jax for its executable
    again: the cfg's FIRST job in the owner compiles (the daemon's cache
    is a new tmp dir), each later edit takes the search program and the
    host-keys program from the process's registry (`serve.program_hits`,
    origin `held` with the first job's bytes) — and still builds, searches
    from the init states and writes its own finalized checkpoint."""
    edits = sorted((j for k, j in stream["jobs"].items()
                    if j["kind"] == "edit" and k[2] == label),
                   key=lambda j: j["rec"]["finished_at"])
    # (by `finished_at`: the owner is serial, so the job it ran first
    # ended first; two workers mark `started_at` before either has the
    # owner's pipe, in either order)
    assert len(edits) == 6
    first, later = edits[0], edits[1:]

    def programs(job):
        return {p["site"]: p for p in job["art"]["prof"]["programs"]}

    made = programs(first)
    assert made["bfs.resident_run"]["origin"] == "compiled"
    assert first["art"]["serve"]["program_hits"] == 0
    for job in later:
        art, sv = job["art"], job["art"]["serve"]
        assert sv["warm_engine"] is False
        assert sv["resumed_from_checkpoint"] is False
        assert sv["program_hits"] >= 2         # host_keys and run
        assert sv["window_recompiles"] == 0
        assert sv["persistent_cache_hits"] == 0   # nothing was loaded
        assert art["counters"]["compile.xla_compile_s"] == 0.0
        assert "compile.program_misses" not in art["counters"]
        got = programs(job)
        assert set(got) == {"bfs.host_keys", "bfs.resident_run"}
        for site, p in got.items():
            assert p["origin"] == "held" and p["xla_s"] == 0.0
            assert p["dispatches"] >= 1
            assert p["key"] == made[site]["key"]
            assert p["hbm_bytes"] == made[site]["hbm_bytes"]
        assert art["gauges"]["program.hbm_bytes"] == \
            made["bfs.resident_run"]["hbm_bytes"]
        phases = {p["name"] for p in art["phases"]}
        assert {"engine_build", "search", "checkpoint.write"} <= phases
        assert _searched(art["result"]) == _searched(first["art"]["result"])


def test_the_in_process_path_holds_programs_too(tmp_path, monkeypatch):
    """`JAXMC_SERVE_DEVICE_OWNER=0`: the daemon's worker thread builds the
    engines in the daemon's own process, whose registry it is.  A stamped
    resubmission is cold (a build, a full search, its own checkpoint) on
    the program the first job made; a byte-identical one replays warm."""
    from jaxmc.serve import ServeDaemon
    lib = _bench("lib.py")
    monkeypatch.setenv("JAXMC_SERVE_DEVICE_OWNER", "0")
    monkeypatch.setenv("JAXMC_LEDGER", "off")
    monkeypatch.setenv("JAXMC_PROFILE_STORE", str(tmp_path / "profiles"))
    cfg = str(tmp_path / "2p3.cfg")
    with open(cfg, "w", encoding="utf-8") as fh:
        fh.write(SUITE["2p3"])
    text = open(SPEC, encoding="utf-8").read()
    commits = []
    for k in (1, 2):
        d = tmp_path / f"commit-{k}"
        d.mkdir()
        commits.append(str(d / os.path.basename(SPEC)))
        with open(commits[-1], "w", encoding="utf-8") as fh:
            fh.write(lib.stamp_spec(text, f"in-process commit {k}"))
    daemon = ServeDaemon(str(tmp_path / "spool"), workers=1,
                         quiet=True).start()
    try:
        assert daemon.owner is None
        client = ServeClient("127.0.0.1", daemon.port)
        arts, sigs = [], []
        for spec_path in (commits[0], commits[1], commits[1]):
            code, body = client.submit(spec_path, cfg, OPTS, tenant="ci-a")
            assert code == 200, (code, body)
            rec = client.wait(body["id"], timeout=180, poll_s=0.05)
            assert rec["status"] == "done", rec
            code, art = client.result(body["id"])
            assert code == 200
            arts.append(art)
            sigs.append(body["sig"])
    finally:
        daemon.shutdown()
    assert sigs[0] != sigs[1] == sigs[2]
    first, stamped, rerun = arts
    assert first["serve"]["program_hits"] == 0
    sv = stamped["serve"]
    assert not sv.get("device_owner")
    assert sv["warm_engine"] is False
    assert sv["resumed_from_checkpoint"] is False
    assert sv["program_hits"] >= 2 and sv["window_recompiles"] == 0
    assert {p["origin"] for p in stamped["prof"]["programs"]} == {"held"}
    assert {"engine_build", "search", "checkpoint.write"} <= \
        {p["name"] for p in stamped["phases"]}
    assert rerun["serve"]["warm_engine"] is True
    assert rerun["serve"]["resumed_from_checkpoint"] is True
    assert rerun["serve"]["program_hits"] == 0     # nothing dispatched
    assert _searched(first["result"]) == _searched(stamped["result"]) == \
        _searched(rerun["result"]) == (256, 166, 6)


def test_daemon_and_owner_are_gone(stream):
    assert stream["rc"] == 0
    pid = stream["owner_pid"]
    for _ in range(200):
        if not os.path.exists(f"/proc/{pid}"):
            break
        time.sleep(0.05)
    assert not os.path.exists(f"/proc/{pid}")


@pytest.mark.parametrize("count", ["generated", "distinct", "diameter"])
def test_one_changed_count_fails_the_comparison(stream, answers, count):
    lib = _bench("lib.py")
    job = stream["jobs"][("ci-a", 0, "3p2")]
    plain = answers["3p2"][1]
    good = dict(job["art"]["result"])
    assert lib.compare(good, plain, "as answered")
    assert not lib.compare(dict(good, **{count: good[count] + 1}), plain,
                           f"{count} + 1")
    assert not lib.compare(dict(good, truncated=True), plain, "truncated")


def test_owner_loop_on_a_pipe(monkeypatch):
    """The owner's request loop in THIS process, on a pipe: it answers a
    ping, runs a solo job and says what served it, reports a request it
    does not know instead of dying, and stops when told."""
    import multiprocessing as mp

    from jaxmc.serve import owner

    monkeypatch.setattr("signal.signal", lambda *a: None)
    here, there = mp.Pipe()
    t = threading.Thread(target=owner._owner_main, args=(there,))
    t.start()
    try:
        here.send({"kind": "ping"})
        assert here.recv()["pong"]
        here.send({"kind": "solo", "member": {
            "spec": os.path.join(REPO, "specs", "viewtoy.tla"),
            "cfg": None, "options": {"backend": "interp", "workers": 1},
            "sig": "s1", "jids": ["j1"]}})
        resp = here.recv()
        assert resp.get("ok") is True, resp
        sv = resp["summary"]["serve"]
        assert sv["sig"] == "s1" and sv["device_owner"] is True
        assert sv["warm_engine"] is False and sv["job_wall_s"] > 0
        here.send({"kind": "nonsense"})
        assert "unknown request" in here.recv()["error"]
    finally:
        here.send({"kind": "stop"})
        assert here.recv() == {"stopped": True}
        t.join(timeout=30)
    assert not t.is_alive()
