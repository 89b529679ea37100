r"""A served job's STATIONS (ISSUE 49; serve/protocol.py "A job's clock"):
every second between the POST's record and the verdict's lies between two
wall-clock marks of the program's own, in the job's record AND in its
artifact's `serve.stations`.

Three daemons in this process, at toy size on XLA:CPU, one scenario each
(module fixtures; one case per job and property, so that each counts):

  `owned`   two workers, the device owner ON: a cold solo job (the request
            that SPAWNS the owner), its warm replay, two different jobs
            submitted together (the second stands on `DeviceOwner._lock`
            for the whole of the first), two identical ones behind them (a
            leader and its exact-signature follower), an interp job and
            its replay (answered in the daemon: no owner station);
  `cohort`  a cold spool primed with three layout-compatible jobs: one
            vbatch, every member carrying the cohort's owner stations;
  `inproc`  `JAXMC_SERVE_DEVICE_OWNER=0`: the daemon's own thread runs the
            device job, and no owner station exists to be stamped.
"""

import hashlib
import inspect
import io
import json
import os
import time

import pytest

from jaxmc import drain
from jaxmc.serve import JobQueue, ServeDaemon
from jaxmc.serve.protocol import (STATIONS, ServeClient, build_config,
                                  job_signature)
from jaxmc.session import batch_profile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = os.path.join(REPO, "specs")
TRANSFER = os.path.join(REPO, "bench", "specs", "transfer_scaled.tla")
RESIDENT = {"backend": "jax", "platform": "cpu", "resident": True,
            "no_trace": True}
HOST_SEEN = {"backend": "jax", "platform": "cpu", "host_seen": True}
OWNER = ("owner_sent_at", "owner_began_at", "owner_ended_at",
         "owner_received_at")
SECONDS = ("owner_wait_s", "owner_envelope_s", "publish_s")


def _cfg(tmp, procs, money):
    path = os.path.join(tmp, f"t{procs}p{money}.cfg")
    with open(path, "w", encoding="utf-8") as fh:
        fh.write("SPECIFICATION Spec\nINVARIANT AliceBounded\nCONSTANTS\n"
                 f"  Procs = {{{', '.join(f'p{i + 1}' for i in range(procs))}"
                 f"}}\n  MaxMoney = {money}\n")
    return path


def _submit(c, spec, cfg, opts):
    code, body = c.submit(spec, cfg, opts)
    assert code == 200, (code, body)
    return body["id"]


def _collect(d, c, ids, timeout=300):
    """{name: {"rec", "art", "trace"}} once every job has ended."""
    out = {}
    for name, jid in ids.items():
        rec = c.wait(jid, timeout=timeout)
        assert rec["status"] == "done", (name, rec)
        out[name] = {"rec": d.q.load(jid), "art": d.q.load_result(jid),
                     "http": rec, "id": jid,
                     "trace": os.path.join(d.q.results_dir,
                                           f"{jid}.trace.jsonl")}
    return out


@pytest.fixture(scope="module")
def _env():
    mp = pytest.MonkeyPatch()
    drain.clear()
    yield mp
    mp.undo()
    drain.clear()


@pytest.fixture(scope="module")
def owned(_env, tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("stations"))
    _env.setenv("JAXMC_SERVE_DEVICE_OWNER", "1")
    _env.setenv("JAXMC_PROFILE_STORE", os.path.join(tmp, "profiles"))
    d = ServeDaemon(os.path.join(tmp, "spool"), workers=2,
                    trace=os.path.join(tmp, "daemon.trace.jsonl"),
                    quiet=True).start()
    try:
        c = ServeClient("127.0.0.1", d.port)
        cold = _submit(c, TRANSFER, _cfg(tmp, 2, 2), RESIDENT)
        jobs = _collect(d, c, {"cold": cold})
        jobs.update(_collect(d, c, {"warm": _submit(
            c, TRANSFER, _cfg(tmp, 2, 2), RESIDENT)}))
        # two different jobs together, one a worker; then two identical
        # ones, which wait in the queue until a worker is free again
        ids = {"pair_a": _submit(c, TRANSFER, _cfg(tmp, 2, 3), RESIDENT),
               "pair_b": _submit(c, TRANSFER, _cfg(tmp, 3, 2), RESIDENT)}
        same = _cfg(tmp, 3, 1)
        ids["leader"] = _submit(c, TRANSFER, same, RESIDENT)
        ids["follower"] = _submit(c, TRANSFER, same, RESIDENT)
        jobs.update(_collect(d, c, ids))
        interp = {"backend": "interp"}
        jobs.update(_collect(d, c, {"interp": _submit(
            c, os.path.join(SPECS, "constoy.tla"), None, interp)}))
        jobs.update(_collect(d, c, {"interp_replay": _submit(
            c, os.path.join(SPECS, "constoy.tla"), None, interp)}))
        ckpt = d.q.ckpt_path(jobs["cold"]["rec"]["sig"])
        yield {"jobs": jobs, "ckpt_bytes": os.path.getsize(ckpt),
               "spawns": d.owner.spawns,
               "daemon_phases": {p["name"]: p for p in d.tel.phase_list()}}
    finally:
        d.shutdown()


@pytest.fixture(scope="module")
def cohort(_env, tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("stations_cohort"))
    _env.setenv("JAXMC_SERVE_DEVICE_OWNER", "1")
    _env.setenv("JAXMC_PROFILE_STORE", os.path.join(tmp, "profiles"))
    spool = os.path.join(tmp, "spool")
    q, ids = JobQueue(spool), {}
    bt = os.path.join(SPECS, "batchtoy.tla")
    for v in ("a", "b", "c"):
        cfg = build_config(bt, os.path.join(SPECS, f"batchtoy_{v}.cfg"),
                           HOST_SEEN)
        prof = batch_profile(cfg)
        ids[v] = q.new_job(cfg.spec, cfg.cfg, HOST_SEEN,
                           job_signature(cfg), bsig=prof.bsig,
                           cost_estimate=prof.cost_estimate)["id"]
    d = ServeDaemon(spool, workers=2, quiet=True).start()
    try:
        yield _collect(d, ServeClient("127.0.0.1", d.port), ids)
    finally:
        d.shutdown()


@pytest.fixture(scope="module")
def inproc(_env, tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("stations_inproc"))
    _env.setenv("JAXMC_SERVE_DEVICE_OWNER", "0")
    _env.setenv("JAXMC_PROFILE_STORE", os.path.join(tmp, "profiles"))
    d = ServeDaemon(os.path.join(tmp, "spool"), workers=1,
                    quiet=True).start()
    try:
        assert d.owner is None
        c = ServeClient("127.0.0.1", d.port)
        yield _collect(d, c, {"solo": _submit(
            c, TRANSFER, _cfg(tmp, 2, 2), RESIDENT)})
    finally:
        d.shutdown()


OWNED = ("cold", "warm", "pair_a", "pair_b", "leader", "follower")
MEMBERS = ("a", "b", "c")


def _owner_jobs(owned, cohort):
    return [(n, owned["jobs"][n]) for n in OWNED] + \
        [(n, cohort[n]) for n in MEMBERS]


@pytest.mark.parametrize("name", OWNED + MEMBERS)
def test_every_station_is_there_and_in_order(owned, cohort, name):
    job = dict(_owner_jobs(owned, cohort))[name]
    st = job["art"]["serve"]["stations"]
    want = [k for k in STATIONS if k != "owner_spawned_at"]
    assert [k for k in st if k != "owner_spawned_at"] == want
    marks = [st[k] for k in STATIONS if k in st]
    assert marks == sorted(marks), st
    assert all(isinstance(v, float) for v in marks)
    # `started_at` keeps its name and its value: the worker's claim
    assert job["rec"]["started_at"] == st["claimed_at"]
    assert job["rec"]["submitted_at"] == st["submitted_at"]


@pytest.mark.parametrize("name", OWNED + MEMBERS)
def test_the_stations_telescope(owned, cohort, name):
    """Queue, owner wait, envelope, run and publish ARE the record's
    wall: nothing of it lies between no two marks."""
    job = dict(_owner_jobs(owned, cohort))[name]
    sv = job["art"]["serve"]
    st = sv["stations"]
    parts = (st["claimed_at"] - st["submitted_at"]) + sv["owner_wait_s"] \
        + sv["owner_envelope_s"] + sv["job_wall_s"] + sv["publish_s"]
    assert parts == pytest.approx(st["finished_at"] - st["submitted_at"],
                                  abs=1e-3)
    assert all(sv[k] >= 0 for k in SECONDS), sv
    assert sv["owner_wait_s"] == pytest.approx(
        st["owner_sent_at"] - st["claimed_at"], abs=1e-5)
    # the owner's own two marks hold its `job_wall_s` (it is computed from
    # them) and lie inside the daemon's two
    assert st["owner_ended_at"] - st["owner_began_at"] == pytest.approx(
        sv["job_wall_s"], abs=1e-5)


@pytest.mark.parametrize("name", OWNED + MEMBERS + ("interp",
                                                   "interp_replay"))
def test_record_and_artifact_agree_on_every_station(owned, cohort, name):
    job = dict(_owner_jobs(owned, cohort) + [
        (n, owned["jobs"][n]) for n in ("interp", "interp_replay")])[name]
    st = job["art"]["serve"]["stations"]
    assert st and set(st) <= set(STATIONS)
    for k, v in st.items():
        assert job["rec"][k] == v, k
    # and the record holds no station the artifact lacks
    assert {k for k in STATIONS if k in job["rec"]} == set(st)
    # GET /jobs/<id> shows them, and the `serve` block beside them
    assert {k: job["http"][k] for k in st} == st
    assert job["http"]["serve"]["stations"] == st


def test_the_request_that_spawned_the_owner_says_so(owned):
    jobs = owned["jobs"]
    cold, sv = jobs["cold"]["art"]["serve"]["stations"], \
        jobs["cold"]["art"]["serve"]
    assert cold["claimed_at"] <= cold["owner_spawned_at"] <= \
        cold["owner_sent_at"]
    assert sv["owner_spawn_s"] == pytest.approx(
        cold["owner_began_at"] - cold["owner_spawned_at"], abs=1e-5)
    # the child's coming up is in THIS job's envelope, and nowhere else
    assert 0 < sv["owner_spawn_s"] <= sv["owner_envelope_s"] + 1e-3
    assert owned["spawns"] == 1
    for name in OWNED[1:]:
        other = jobs[name]["art"]["serve"]
        assert "owner_spawned_at" not in other["stations"], name
        assert "owner_spawned_at" not in jobs[name]["rec"], name
        assert "owner_spawn_s" not in other, name


def test_the_warm_replay_went_through_the_owner(owned):
    sv = owned["jobs"]["warm"]["art"]["serve"]
    assert sv["warm_engine"] and sv["resumed_from_checkpoint"]
    assert sv["device_owner"] is True
    assert sv["job_wall_s"] < owned["jobs"]["cold"]["art"]["serve"][
        "job_wall_s"]


def test_a_claimed_job_waits_for_the_owner_not_in_the_queue(owned):
    """Two workers, one owner, two jobs together: each worker claims one at
    once (`started_at`), and the second then waits one whole job on the
    owner's lock — `owner_wait_s`, which `started_at - submitted_at` does
    not hold."""
    first, second = sorted(
        (owned["jobs"][n] for n in ("pair_a", "pair_b")),
        key=lambda j: j["art"]["serve"]["stations"]["owner_began_at"])
    f, s = first["art"]["serve"], second["art"]["serve"]
    assert s["owner_wait_s"] >= f["job_wall_s"] - 0.1
    queue_wait = second["rec"]["started_at"] - second["rec"]["submitted_at"]
    assert queue_wait < 0.5 * f["job_wall_s"]
    assert f["owner_wait_s"] < 0.5 * f["job_wall_s"]
    # the serial owner: the second began after the first ended
    assert s["stations"]["owner_began_at"] >= f["stations"]["owner_ended_at"]
    assert s["stations"]["claimed_at"] < f["stations"]["owner_ended_at"]


def test_a_follower_carries_its_leaders_owner_stations(owned):
    lead, foll = owned["jobs"]["leader"], owned["jobs"]["follower"]
    assert foll["rec"]["batch_leader"] == lead["id"]
    ls, fs = (j["art"]["serve"]["stations"] for j in (lead, foll))
    assert {k: fs[k] for k in OWNER} == {k: ls[k] for k in OWNER}
    assert fs["claimed_at"] == ls["claimed_at"]
    # its own record's marks are its own
    assert fs["submitted_at"] > ls["submitted_at"]
    assert fs["enqueued_at"] > ls["enqueued_at"]
    assert fs["finished_at"] > ls["finished_at"]
    assert foll["art"]["serve"]["publish_s"] > \
        lead["art"]["serve"]["publish_s"]


def test_every_member_of_a_vbatch_carries_the_cohorts(cohort):
    sts = [cohort[n]["art"]["serve"]["stations"] for n in MEMBERS]
    for st in sts[1:]:
        assert {k: st[k] for k in OWNER + ("claimed_at",)} == \
            {k: sts[0][k] for k in OWNER + ("claimed_at",)}
    for n in MEMBERS:
        sv = cohort[n]["art"]["serve"]
        assert sv["batch_occupancy"] == 3 and sv["device_owner"] is True
    # the members are published one after the other
    fins = sorted(st["finished_at"] for st in sts)
    assert fins[0] < fins[1] < fins[2]
    # the cohort's request spawned this daemon's owner
    assert all("owner_spawned_at" in st for st in sts)


@pytest.mark.parametrize("which", ["interp", "interp_replay", "inproc"])
def test_no_owner_no_owner_station(owned, inproc, which):
    """A job the daemon's own thread answered — an interp job beside a live
    owner, its warm replay, a device job with the owner switched off —
    carries the daemon's four stations and NO owner station or second: a
    station is left out, never faked."""
    job = inproc["solo"] if which == "inproc" else owned["jobs"][which]
    sv = job["art"]["serve"]
    assert list(sv["stations"]) == ["submitted_at", "enqueued_at",
                                    "claimed_at", "finished_at"]
    marks = list(sv["stations"].values())
    assert marks == sorted(marks)
    assert not [k for k in sv if k.startswith("owner_") or k == "publish_s"]
    assert not [k for k in job["rec"] if k.startswith("owner_")]
    assert not sv.get("device_owner")
    if which == "interp_replay":
        assert sv["warm_engine"]


def test_checkpoint_bytes_is_the_files_size(owned):
    """`checkpoint.write` says how many bytes it wrote, as the span's
    attribute and as the counter `checkpoint.bytes`: the size of the
    finalized checkpoint on disk."""
    cold = owned["jobs"]["cold"]
    assert cold["art"]["counters"]["checkpoint.bytes"] == \
        owned["ckpt_bytes"] > 0
    spans = [json.loads(ln) for ln in open(cold["trace"], encoding="utf-8")]
    writes = [e for e in spans if e.get("ev") == "span"
              and e.get("name") == "checkpoint.write"]
    assert [e["attrs"]["bytes"] for e in writes] == [owned["ckpt_bytes"]]
    # a replay reads the checkpoint and writes none
    assert "checkpoint.bytes" not in owned["jobs"]["warm"]["art"]["counters"]


def test_interp_checkpoints_count_their_bytes_too(tmp_path):
    from jaxmc import obs
    from jaxmc.engine import ckpt
    tel = obs.Telemetry()
    path = str(tmp_path / "x.ck")
    assert ckpt.write_periodic(path, "interp", {"module": "m"},
                               {"states": list(range(100))}, tel,
                               lambda *_: None, {"every": 60.0})
    assert tel.counters["checkpoint.bytes"] == os.path.getsize(path)
    tel.close()


def test_the_owner_has_one_envelope_span_a_job(owned, cohort):
    """`job` in `run_solo`, `vbatch` in `run_vbatch`, on the job's (the
    leader's) own recorder: it holds every other span of the job."""
    for name in OWNED[:4]:
        art = owned["jobs"][name]["art"]
        ph = {p["name"]: p for p in art["phases"]}
        assert ph["job"]["count"] == 1 and "vbatch" not in ph
        assert art["phases"][0]["name"] == "job"
        assert ph["job"]["wall_s"] <= art["serve"]["job_wall_s"]
        assert ph["job"]["wall_s"] >= ph["search"]["wall_s"]
    leader = min(MEMBERS,
                 key=lambda n: cohort[n]["art"]["serve"]["stations"][
                     "submitted_at"])
    for n in MEMBERS:
        ph = {p["name"]: p for p in cohort[n]["art"]["phases"]}
        assert ("vbatch" in ph) == (n == leader), n
        assert "job" not in ph
    ph = {p["name"]: p for p in cohort[leader]["art"]["phases"]}
    assert ph["vbatch"]["wall_s"] >= ph["batch.run"]["wall_s"]


def test_the_daemons_job_span_is_cut_in_wait_and_run(owned):
    """In the daemon's own recorder `job` keeps its name and has two
    children: the wait for the owner and the owner's run."""
    ph = owned["daemon_phases"]
    n = ph["job.owner_run"]["count"]
    assert n == ph["job.owner_wait"]["count"] == 5   # five owner requests
    assert ph["job"]["count"] == n + 2               # and two interp jobs
    assert ph["job.owner_wait"]["wall_s"] + ph["job.owner_run"]["wall_s"] \
        <= ph["job"]["wall_s"]
    pair = sum(owned["jobs"][k]["art"]["serve"]["owner_wait_s"]
               for k in OWNED)
    assert ph["job.owner_wait"]["wall_s"] <= pair


def test_report_prints_one_stations_line(owned):
    from jaxmc.obs.report import cmd_report

    class Args:
        file = None
    for name, want in (("pair_b", "owner wait"), ("interp", "no owner")):
        Args.file = os.path.join(
            os.path.dirname(owned["jobs"][name]["trace"]),
            owned["jobs"][name]["id"] + ".json")
        out = io.StringIO()
        assert cmd_report(Args, out=out) == 0
        lines = [ln for ln in out.getvalue().splitlines()
                 if ln.startswith("stations: ")]
        assert len(lines) == 1 and want in lines[0], out.getvalue()
        assert lines[0].startswith("stations: queue ")
    assert "envelope" in lines[0] or "no owner" in lines[0]


def test_the_protocol_and_the_schema_name_what_was_added():
    from jaxmc.obs import schema
    from jaxmc.serve import protocol
    doc = protocol.__doc__
    for word in STATIONS + SECONDS + ("owner_spawn_s", "stations"):
        assert word in doc, word
    assert "may not have begun" in doc
    for word in ("checkpoint.bytes", "job.owner_wait", "job.owner_run",
                 "vbatch"):
        assert word in schema.__doc__, word


def test_new_job_stamps_enqueued_after_the_hard_write(tmp_path):
    q = JobQueue(str(tmp_path / "spool"))
    t0 = time.time()
    job = q.new_job("s.tla", None, {}, "sig")
    assert t0 <= job["submitted_at"] <= job["enqueued_at"] <= time.time()
    assert q.load(job["id"]) == job


def test_owner_main_is_byte_for_byte_what_it_was():
    """`_owner_main`'s loop is not to be touched without measuring first
    (PERF.md section 7 (e): a line there once cost 0.13 s a launch); the
    stations are values in a dict `run_solo` / `run_vbatch` hand on."""
    from jaxmc.serve import owner
    src = inspect.getsource(owner._owner_main)
    assert hashlib.sha256(src.encode()).hexdigest() == OWNER_MAIN_SHA256, \
        "serve/owner.py::_owner_main changed: measure it first (PERF.md)"


OWNER_MAIN_SHA256 = \
    "d9213f5b111b89996467a69faeb54b6ac74d74ea994433abc9630e7d39edd706"
