r"""A commit's constant matrix as ONE vmapped cohort on the served path
(ISSUE 39), at toy size on XLA:CPU: one real daemon process with its
device-owner child, three runners (tenants `ci-a`, `ci-b`, `ci-c`), each a
closed loop through `edit, edit`; a commit is four jobs, Procs = {p1, p2, p3}
x MaxMoney 2, 3, 4, 5 of `bench/specs/transfer_scaled.tla`, options
`host_seen` and `no_trace` — the benchmark cell `ci-cohort-4p` in small.  Two
primer jobs hold both workers while every runner's warm-up commit is POSTed
whole behind them, and each runner goes from its warm-up's verdicts to its
window without a gap (`bench/drivers/cohort.py` does the same).

Held here, one parametrised case per job or commit so that each counts:
  - every verdict equals the exact interpreter's, the benchmark's plain
    reference's (`bench/reference/transfer_scaled.py`) and a solo `host_seen`
    engine's, answered cold and in the owner — which a cohort's `serve`
    block did not say before this PR;
  - every window commit ran as one cohort of four with its three
    commit-mates in `batched_with` and ONE engine build;
  - the cohort's spans and counters reach the client in the leader's
    artifact, the members' in each member's, and they account for the
    commit's `job_wall_s` and for every member's `search`;
  - what the cell rests on: `MaxMoney` lifts, the four cfgs share a `bsig`;
  - a verdict with one count changed fails the benchmark's comparison;
  - the owner did not die, nothing was refused, nothing is left running.
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
from jaxmc.serve.protocol import ServeClient, build_config
from jaxmc.session import CheckSession, batch_profile, load_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench")
SPEC = os.path.join(BENCH, "specs", "transfer_scaled.tla")
MATRIX = {f"3p{m}": "SPECIFICATION Spec\nINVARIANT AliceBounded\n"
                    "CONSTANTS\n  Procs = {p1, p2, p3}\n"
                    f"  MaxMoney = {m}\n" for m in (2, 3, 4, 5)}
RUNNERS = ("ci-a", "ci-b", "ci-c")
CYCLE = ("edit", "edit")
OPTS = {"backend": "jax", "platform": "cpu", "host_seen": True,
        "no_trace": True}
#: every window commit, and every job of them
COMMITS = [(r, k) for r in RUNNERS for k in (1, 2)]
JOBS = [(r, k, c) for r, k in COMMITS for c in MATRIX]
ENDED = ("done", "failed", "drained", "quarantined")


@functools.lru_cache(maxsize=None)
def _bench(name):
    """A module of bench/ by file (bench/ is no package)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(name)[:-3], os.path.join(BENCH, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _commit_spec(work, tenant, k):
    d = os.path.join(work, "commits", f"{tenant}-{k}")
    os.makedirs(d)
    path = os.path.join(d, os.path.basename(SPEC))
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(_bench("lib.py").stamp_spec(
            open(SPEC, encoding="utf-8").read(), f"{tenant} commit {k}"))
    return path


def _post(client, tenant, k, spec_path, cfgs):
    jobs = []
    for label, cfg_path in cfgs.items():
        job = {"runner": tenant, "commit": k, "label": label,
               "t_post": time.time()}
        code, body = client.submit(spec_path, cfg_path, OPTS, tenant=tenant)
        assert code == 200, (code, body)
        job.update(id=body["id"], sig=body["sig"], bsig=body.get("bsig"))
        jobs.append(job)
    return jobs


def _await(client, jobs):
    inflight, deadline = list(jobs), time.time() + 300
    while inflight:
        assert time.time() < deadline, "no verdict in 300 s"
        for job in list(inflight):
            code, rec = client.job(job["id"])
            if code == 200 and rec.get("status") in ENDED:
                code, art = client.result(job["id"])
                job.update(rec=rec, art=art if code == 200 else None,
                           t_result=time.time())
                inflight.remove(job)
        if inflight:
            time.sleep(0.05)


def _runner(client, tenant, work, cfgs, warm, out, errors):
    """A closed loop, one commit in flight: the warm-up's verdicts, then
    the window's commits without a gap."""
    try:
        _await(client, warm)
        for k in range(1, len(CYCLE) + 1):
            jobs = _post(client, tenant, k, _commit_spec(work, tenant, k),
                         cfgs)
            _await(client, jobs)
            for job in jobs:
                out[(tenant, k, job["label"])] = job
    except BaseException as ex:  # noqa: BLE001 — re-raised by the fixture
        errors.append(ex)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    work = str(tmp_path_factory.mktemp("cohort"))
    spool, cache = os.path.join(work, "spool"), os.path.join(work, "cache")
    cfgs = {}
    for label, text in MATRIX.items():
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
    jobs, errors, status, owner_pid, primer, warm = {}, [], {}, None, [], {}
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
        # two primer jobs, each awaited until a worker took it: they bring
        # the owner up and hold both workers ...
        spec0 = _commit_spec(work, "primer", 0)
        for label in list(cfgs)[:2]:
            job = _post(client, "primer", 0, spec0,
                        {label: cfgs[label]})[0]
            primer.append(job)
            while client.job(job["id"])[1].get("status") == "queued":
                time.sleep(0.01)
        # ... and every runner's warm-up commit stands whole behind them
        for t in RUNNERS:
            warm[t] = _post(client, t, 0, _commit_spec(work, t, 0), cfgs)
        threads = [threading.Thread(target=_runner, args=(
            client, t, work, cfgs, warm[t], jobs, errors)) for t in RUNNERS]
        for t in threads:
            t.start()
        _await(client, primer)
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
            "owner_pid": owner_pid, "primer": primer, "warm": warm}


@pytest.fixture(scope="module")
def answers():
    """{cfg label: (the exact interpreter's result, the plain reference's,
    a solo host_seen engine's)} — three yardsticks; the first two share no
    code with the engines."""
    ref = _bench(os.path.join("reference", "transfer_scaled.py"))
    out = {}
    for label, text in MATRIX.items():
        n, m, invs = ref.parse_cfg(text)
        assert invs
        with tempfile.NamedTemporaryFile("w", suffix=".cfg") as fh:
            fh.write(text)
            fh.flush()
            exact = Explorer(load_model(SPEC, fh.name, False)).run()
            sess = CheckSession(build_config(SPEC, fh.name, OPTS))
            sess.parse()
            sess.compile()
            solo = sess.explore()
        out[label] = (exact, ref.explore(n, m), solo)
    return out


def _ids(key):
    return "-".join(str(k) for k in key)


def _counts(res):
    return (res["generated"], res["distinct"], res["diameter"])


def _leader(served, runner, k):
    """(the member whose artifact holds the cohort's spans, the others)."""
    members = [served["jobs"][(runner, k, c)] for c in MATRIX]
    lead = [j for j in members
            if "batch.build" in {p["name"] for p in j["art"]["phases"]}]
    assert len(lead) == 1, [j["id"] for j in lead]
    return lead[0], [j for j in members if j is not lead[0]]


@pytest.mark.parametrize("key", JOBS, ids=_ids)
def test_verdict_equals_interpreter_reference_and_solo(served, answers, key):
    job = served["jobs"][key]
    assert job["rec"]["status"] == "done" and job["art"], job["rec"]
    res = job["art"]["result"]
    exact, plain, solo = answers[key[2]]
    assert _counts(res) == (exact.generated, exact.distinct, exact.diameter)
    assert _counts(res) == _counts(plain)
    assert _counts(res) == (solo.generated, solo.distinct, solo.diameter)
    assert res["ok"] is True and exact.ok and plain["ok"] and solo.ok
    assert res["truncated"] is False and res["finished_on"] == "jax"
    # the benchmark's own comparison says the same
    assert _bench("lib.py").compare(res, plain, _ids(key))


@pytest.mark.parametrize("key", JOBS, ids=_ids)
def test_answered_cold_in_the_owner_and_says_so(served, key):
    job = served["jobs"][key]
    sv, rec = job["art"]["serve"], job["rec"]
    assert sv["warm_engine"] is False
    assert sv["resumed_from_checkpoint"] is False
    # a cohort's block and record say where it ran, as a solo job's do
    assert sv["device_owner"] is True and rec["device_owner"] is True
    assert sv["lifted_consts"] == ["MaxMoney"]
    assert "checkpoint.write" in {p["name"] for p in job["art"]["phases"]}


@pytest.mark.parametrize("commit", COMMITS, ids=_ids)
def test_a_window_commit_is_one_cohort_of_the_matrix(served, commit):
    members = [served["jobs"][commit + (c,)] for c in MATRIX]
    ids = {j["id"] for j in members}
    assert len(ids) == 4 and len({j["bsig"] for j in members}) == 1
    assert len({j["sig"] for j in members}) == 4
    for j in members:
        sv = j["art"]["serve"]
        assert sv["batch_occupancy"] == 4 == j["rec"]["batch_occupancy"]
        assert set(sv["batched_with"]) == ids - {j["id"]}
        assert sv["bsig"] == j["bsig"]
        assert sv["job_wall_s"] == members[0]["art"]["serve"]["job_wall_s"]
        assert sv["batch_dispatches"] == \
            members[0]["art"]["serve"]["batch_dispatches"]
    # ONE engine build: the donor's, in the leader's artifact alone
    lead, rest = _leader(served, *commit)
    phases = {p["name"]: p for p in lead["art"]["phases"]}
    assert phases["engine_build"]["count"] == 1
    assert lead["art"]["counters"]["compile.kernels_built"] > 0
    for j in rest:
        assert "engine_build" not in {p["name"] for p in j["art"]["phases"]}
        assert "compile.kernels_built" not in j["art"]["counters"]


@pytest.mark.parametrize("commit", COMMITS, ids=_ids)
def test_the_cohorts_spans_and_counters_reach_the_client(served, commit):
    lead, rest = _leader(served, *commit)
    art, sv = lead["art"], lead["art"]["serve"]
    ph = {p["name"]: p for p in art["phases"]}
    c = art["counters"]
    n = sv["batch_dispatches"]
    assert {"batch.build", "load", "batch_sample", "engine_build",
            "batch.run", "search", "checkpoint.write"} <= set(ph)
    assert ph["batch.dispatch"]["count"] == n
    assert c["batch.dispatches"] == n
    # ONE device-to-host transfer a superstep (ISSUE 40; nine before)
    assert c["batch.fetch_transfers"] == n
    assert c["batch.fetch_mb"] > 0
    assert n <= c["batch.lane_steps"] <= 4 * n
    for name in ("batch.stack_s", "batch.unstack_s", "batch.upload_s",
                 "batch.fetch_s", "batch.first_dispatch_s"):
        assert c[name] > 0.0, name
    # the vmapped program's site and record are the cohort's, whichever
    # member's thread fired
    assert art["prof"]["sites"]["batch.vstep"]["dispatches"] == n
    assert [p["site"] for p in art["prof"]["programs"]
            if p["site"] == "batch.vstep"] == ["batch.vstep"]
    # a ragged cohort's narrower supersteps gather their live lanes on
    # the device before the one fetch (ISSUE 40)
    assert 0 < art["prof"]["sites"]["batch.take"]["dispatches"] < n
    for j in rest:
        assert "batch.vstep" not in j["art"]["prof"]["sites"]
        assert "batch.take" not in j["art"]["prof"]["sites"]
        assert not [k for k in j["art"]["counters"]
                    if k.startswith("batch.") and
                    k != "batch.barrier_wait_s"]
    # a commit's pieces are its wall: the build and the run, to within
    # the four configs and recorders made before them (slack 0.25 s)
    pieces = ph["batch.build"]["wall_s"] + ph["batch.run"]["wall_s"]
    assert 0.0 <= sv["job_wall_s"] - pieces < 0.25, (sv["job_wall_s"],
                                                    pieces)
    fire = ph["batch.dispatch"]["wall_s"] + c["batch.stack_s"] + \
        c["batch.unstack_s"]
    assert c["batch.first_dispatch_s"] < fire < ph["batch.run"]["wall_s"]
    assert c["batch.upload_s"] + c["batch.fetch_s"] < \
        ph["batch.dispatch"]["wall_s"]


@pytest.mark.parametrize("key", JOBS, ids=_ids)
def test_a_members_search_is_accounted_for(served, answers, key):
    art = served["jobs"][key]["art"]
    ph = {p["name"]: p["wall_s"] for p in art["phases"]}
    c = art["counters"]
    assert c["hostseen.chunks"] >= 10          # a chunk a level at least
    # every generated state but the init states goes through the store
    inits = answers[key[2]][1]["levels"][0][0]
    assert c["hostseen.store_keys"] == art["result"]["generated"] - inits
    for name in ("hostseen.step_s", "hostseen.store_s", "hostseen.book_s",
                 "hostseen.tail_s", "batch.barrier_wait_s"):
        assert c[name] >= 0.0, name
    # the barrier wait lies inside the steps; the steps, the store, the
    # bookkeeping, the level tails and the finalized checkpoint are the
    # search (slack: the init states, 0.25 s + a tenth)
    assert c["batch.barrier_wait_s"] <= c["hostseen.step_s"] + 1e-6
    pieces = sum(c["hostseen." + k] for k in
                 ("step_s", "store_s", "book_s", "tail_s")) + \
        ph["checkpoint.write"]
    assert 0.0 <= ph["search"] - pieces < 0.25 + 0.1 * ph["search"], \
        (ph["search"], pieces)


def test_what_the_cell_rests_on_maxmoney_lifts_and_the_cfgs_share_a_bsig(
        served):
    from jaxmc.analyze.bounds import liftable_constants
    bsigs, sigs = set(), set()
    for label, path in served["cfgs"].items():
        assert liftable_constants(load_model(SPEC, path, False)) == \
            ("MaxMoney",), label
        prof = batch_profile(build_config(SPEC, path, OPTS))
        assert prof.lift == ("MaxMoney",)
        bsigs.add(prof.bsig)
    assert len(bsigs) == 1
    # a resident job (ci-stream-4p8's) has no batch profile at all
    assert batch_profile(build_config(
        SPEC, served["cfgs"]["3p5"], dict(OPTS, host_seen=False,
                                          resident=True))) is None
    # a stamped copy is another class: a cohort never spans two commits
    for job in served["jobs"].values():
        sigs.add(job["bsig"])
    assert len(sigs) == len(COMMITS)


def test_the_warm_up_commits_ran_whole_too(served):
    """Behind the two primers every warm-up commit stood whole in the
    queue: the vmapped program is made before the window opens."""
    assert [j["art"]["serve"].get("batch_occupancy")
            for j in served["primer"]] == [None, None]
    for tenant, jobs in served["warm"].items():
        assert [j["art"]["serve"].get("batch_occupancy")
                for j in jobs] == [4] * 4, tenant


def test_owner_survived_and_nothing_was_refused(served):
    st, counters = served["status"], served["status"]["counters"]
    assert st["daemon_holds_device"] is False
    assert st["device_owner_pid"]
    for name in ("serve.owner_respawns", "serve.admission_rejected",
                 "serve.batch_incompatible", "serve.batch_solo_retries"):
        assert counters.get(name, 0) == 0, name
    assert st["quarantined"] == 0 and st["jobs_failed"] == 0
    assert counters["serve.jobs_done"] == 2 + 12 + 24
    assert counters["serve.vbatch_jobs"] == 12 + 24
    assert st["gauges"]["serve.batch_occupancy"] == 4
    assert st["gauges"]["serve.batch_compiles"] == 1


def test_daemon_and_owner_are_gone(served):
    assert served["rc"] == 0
    pid = served["owner_pid"]
    for _ in range(200):
        if not os.path.exists(f"/proc/{pid}"):
            break
        time.sleep(0.05)
    assert not os.path.exists(f"/proc/{pid}")


@pytest.mark.parametrize("count", ["generated", "distinct", "diameter"])
def test_one_changed_count_fails_the_comparison(served, answers, count):
    lib = _bench("lib.py")
    job = served["jobs"][("ci-a", 1, "3p5")]
    plain = answers["3p5"][1]
    good = dict(job["art"]["result"])
    assert lib.compare(good, plain, "as answered")
    assert not lib.compare(dict(good, **{count: good[count] + 1}), plain,
                           f"{count} + 1")
    assert not lib.compare(dict(good, truncated=True), plain, "truncated")


def test_the_schema_documents_the_cohorts_names():
    from jaxmc.obs import schema
    doc = open(schema.__file__, encoding="utf-8").read()
    for name in ("batch.build", "batch.run", "batch.dispatch",
                 "batch.stack_s", "batch.unstack_s", "batch.upload_s",
                 "batch.fetch_s", "batch.first_dispatch_s",
                 "batch.fetch_transfers", "batch.fetch_mb",
                 "batch.lane_steps", "batch.barrier_wait_s",
                 "hostseen.chunks", "hostseen.step_s", "hostseen.store_s",
                 "hostseen.store_keys", "hostseen.book_s",
                 "hostseen.tail_s"):
        assert name in doc, name


def test_report_prints_where_a_cohorts_wall_went(served, tmp_path):
    import io

    from jaxmc.obs.report import main as obs_main
    lead, rest = _leader(served, "ci-b", 2)
    for job, want, miss in ((lead, ("cohort: build ", "host_seen: ",
                                    " transfers, "), ()),
                            (rest[0], ("host_seen: ",), ("cohort: ",))):
        path = str(tmp_path / (job["id"] + ".json"))
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(job["art"], fh)
        buf = io.StringIO()
        assert obs_main(["report", path], out=buf) == 0
        for text in want:
            assert text in buf.getvalue(), (text, buf.getvalue())
        for text in miss:
            assert text not in buf.getvalue()
        assert "(barrier wait " in buf.getvalue()
