"""Driver for the `cohort` kind of traffic: a commit's constant matrix against
ONE served daemon, timed by its clients.  This process never imports jax or
jaxmc: the daemon's device-owner child must get the chip, and the clients
speak the HTTP protocol with the standard library alone.

The mix names the runners (tenants), the matrix (the cfgs every commit
checks: one spec, one lifted constant swept) and the cycle every runner
repeats.  A runner is a closed loop with ONE commit in flight: a fresh
`lib.stamp_spec` copy of the spec (a stamp of its own for every seed, runner
and commit index: a new content hash, so a new batch signature and a new
cohort), the matrix POSTed back to back in its order, a poll every `poll_s`
until every verdict is read back through GET /jobs/<id>/result, then the next
commit.  A runner starts cycles until `--seconds` have passed and finishes
the cycle it is in.

A worker that pops a job claims every QUEUED job of the same batch signature
with it (`serve/daemon.py`), and nothing waits for commit-mates: a commit
runs as one vmapped cohort only if it stands whole in the queue when a
worker comes free.  So there is one runner more than the daemon has workers,
and the window is entered from a full pipeline.  Set-up (counted in
`setup_s`): the spool under .bench_work/<cell>/, the daemon started as the
configuration says, its stamp awaited; two PRIMER jobs (the matrix's first
two cfgs, a stamp of their own), each POSTed alone and awaited until its
record says `running` — they bring the owner and the chip up and hold both
workers; then one warm-up commit per runner POSTed whole behind them (each
a cohort as wide as the matrix: the vmapped program is traced, compiled or
loaded before the window).  Every runner then awaits its warm-up's verdicts
and goes on to its first window commit without a gap; the window opens at
the FIRST such POST, so the other runners' warm-up cohorts lie at its head
(in its wall, not in its states).  `states_per_s` = the `generated` of the
window's jobs over the clients' wall from that first POST to the last
result read back.

`correct`: every verdict beside the plain reference's for its cfg, answered
cold, in the owner, exactly one a submission, the owner alive and never
respawned, no 429, quarantine, failure, batch fallback or solo retry, a
clean drain.  Cohort WIDTH is not part of it (batching never changes a
verdict): every commit's width is printed and handed to the readers.

With `--trace 1` the daemon is started through `traced_daemon.py`; the
profiler is switched on in the owner by the first runner to enter the
window.  The daemon and its owner are stopped at the end of every run, on
failure too, and the spool's checkpoints are removed.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import threading
import time

from lib import (BenchFailure, check_pins, child_env, compare, load_module,
                 need, reference_answer, say, work_dir)

HERE = os.path.dirname(os.path.abspath(__file__))
st = load_module(os.path.join(HERE, "stream.py"), "bench_driver_stream")
fc = st.fc


class Gate:
    """The window opens when the first runner comes through."""

    def __init__(self, trace_dir):
        self.trace_dir, self.t_window = trace_dir, None
        self._lock = threading.Lock()

    def enter(self) -> float:
        with self._lock:
            if self.t_window is None:
                if self.trace_dir:
                    fc._touch(self.trace_dir, "start")
                    fc._wait_for(self.trace_dir, "started", 30)
                self.t_window = time.time()
        return self.t_window


class Runner(st.Runner):
    """One CI runner: a tenant, a closed loop, one commit (the whole
    matrix) in flight.  `warm` is its warm-up commit, POSTed by `run()`
    below; `jobs` are the window's."""

    def __init__(self, tenant, ctx, client, suite, options, gate):
        super().__init__(tenant, ctx, client, suite, options, None)
        self.gate, self.warm = gate, []

    def post(self, kind: str, k: int, spec_path: str, items=None) -> list:
        out = []
        for item in items or self.suite:
            job = {"runner": self.tenant, "commit": k, "kind": kind,
                   "label": item["label"], "t_post": time.time()}
            code, body = self.client.call("POST", "/jobs", {
                "spec": spec_path, "cfg": item["cfg_path"],
                "options": self.options, "tenant": self.tenant})
            job.update(t_posted=time.time(), code=code,
                       id=body.get("id") if code == 200 else None,
                       sig=body.get("sig") if code == 200 else None,
                       status="posted" if code == 200
                       else f"refused:{code}", error=None if code == 200
                       else body, art=None, rec={})
            job["t_result"] = job["t_posted"]  # until a verdict is read
            out.append(job)
        return out

    def await_running(self, job) -> None:
        """Until the job's record says a worker took it."""
        deadline = time.time() + self.ctx["mix"]["job_timeout_s"]
        while time.time() < deadline:
            code, rec = self.client.call("GET", f"/jobs/{job['id']}")
            if code == 200 and rec.get("status") != "queued":
                return
            time.sleep(0.01)
        raise BenchFailure(f"no worker took primer job {job['id']}")

    def await_verdicts(self, jobs, k) -> None:
        mix = self.ctx["mix"]
        inflight = [j for j in jobs if j["code"] == 200]
        deadline = time.time() + mix["job_timeout_s"]
        while inflight and time.time() < deadline:
            for job in list(inflight):
                code, rec = self.client.call("GET", f"/jobs/{job['id']}")
                if code == 200 and rec.get("status") in st.ENDED:
                    job.update(t_seen=time.time(), rec=rec,
                               status=rec["status"])
                    if rec["status"] == "done":
                        code, art = self.client.call(
                            "GET", f"/jobs/{job['id']}/result")
                        job["art"] = art if code == 200 else None
                    job["t_result"] = time.time()
                    inflight.remove(job)
            if inflight:
                time.sleep(mix["poll_s"])
        for job in inflight:
            job.update(status="timeout", t_result=time.time())
        need(not inflight, f"runner {self.tenant}: no verdict for commit "
                           f"{k} after {mix['job_timeout_s']}s")

    def run(self) -> None:
        try:
            self.await_verdicts(self.warm, 0)
            stop_at = self.gate.enter() + self.ctx["seconds"]
            k = 0
            while True:
                for step in self.ctx["mix"]["cycle"]:
                    k += 1
                    jobs = self.post(step, k, self.edit(k))
                    self.jobs.extend(jobs)
                    self.await_verdicts(jobs, k)
                    self.commits += 1
                if time.time() >= stop_at:
                    break
        except Exception as ex:  # noqa: BLE001 — reported by run()
            self.error = ex


def _judge(job, refs, owner_on: bool) -> bool:
    """One job's verdict beside the plain reference's for its cfg, and
    beside what the configuration guarantees of the way it was answered."""
    tag = f"{job['runner']}#{job['commit']}.{job['label']}"
    if job["status"] != "done" or not job.get("art"):
        say(f"  compare {tag}: ended {job['status']!r} "
            f"{job.get('error') or (job.get('rec') or {}).get('error')} "
            f"FAILED")
        return False
    art = job["art"]
    good = compare(fc._job_answer(art), refs[job["label"]], tag)
    sv = art.get("serve") or {}
    # a cohort's block says `device_owner` since PR 39; a program from
    # before says nothing there, and the daemon's /status (its owner's
    # pid, `daemon_holds_device` false: with an owner every cohort goes
    # through its pipe, `_run_vbatch`) is what is left to hold it to
    said = "device_owner" in sv
    for name, got, want in (
            ("warm_engine", bool(sv.get("warm_engine")), False),
            ("resumed_from_checkpoint",
             bool(sv.get("resumed_from_checkpoint")), False),
            ("device_owner" if said else
             "device_owner (unsaid by the cohort's block; /status)",
             bool(sv["device_owner"] if said else owner_on), True)):
        good = st._check(f"{tag} {name}", got, want) and good
    return good


def _totals(js):
    """The desk cells' shape (`at_window` / `after`), as `stream` gives
    it: the sum over the jobs of their dispatches by site and of every
    numeric counter, and the `program.*` gauges of the largest program."""
    disp, counters, gauges = {}, {}, {}
    for j in js:
        for site, n in j["dispatches"].items():
            disp[site] = disp.get(site, 0) + n
        for name, v in j["counters"].items():
            if isinstance(v, (int, float)):
                counters[name] = counters.get(name, 0) + v
        if j["gauges"].get("program.hbm_bytes", -1) > \
                gauges.get("program.hbm_bytes", -1):
            gauges = dict(j["gauges"])
    return {"dispatches": disp, "counters": counters, "gauges": gauges}


def _cohorts(window):
    """One row a window commit: how wide it ran."""
    rows = {}
    for j in window:
        row = rows.setdefault((j["runner"], j["commit"]), {
            "runner": j["runner"], "commit": j["commit"], "jobs": 0,
            "occupancy": [], "dispatches": [], "mates": []})
        row["jobs"] += 1
        row["occupancy"].append(j["serve"].get("batch_occupancy") or 1)
        row["dispatches"].append(j["serve"].get("batch_dispatches"))
        row["mates"].append(len(j["serve"].get("batched_with") or []))
    return [rows[k] for k in sorted(rows)]


def run(ctx: dict) -> dict:
    mix, conf, root = ctx["mix"], ctx["config"], ctx["root"]
    rehearsal, trace = ctx["rehearsal"], ctx["trace"]
    platform = "cpu" if rehearsal else conf["session"]["platform"]
    work = work_dir(ctx["cell"]["name"], root)
    spool = os.path.join(work, "spool")
    suite = st._suite(ctx, work)
    ctx = dict(ctx, work=work, spec_text=open(
        os.path.join(root, mix["spec"]), encoding="utf-8").read())
    options = dict(mix["job_options"], backend=conf["session"]["backend"],
                   platform=platform)

    env = child_env(root)
    if rehearsal:
        env.update(JAX_PLATFORMS="cpu", JAXMC_COMPILE_CACHE="off")
    trace_dir = os.path.join(work, "trace")
    argv = [sys.executable] + conf["daemon"] + ["--spool", spool]
    if trace:
        os.makedirs(trace_dir)
        env["BENCH_OWNER_TRACE_DIR"] = trace_dir
        argv = [sys.executable, os.path.join(HERE, "traced_daemon.py")] + \
            conf["daemon"][2:] + ["--spool", spool]
    log = open(os.path.join(work, "daemon.err"), "w")
    daemon = subprocess.Popen(argv, cwd=root, env=env,
                              stdout=subprocess.DEVNULL, stderr=log)
    owner_pid, kids, clean, runners, primer = None, [], False, [], None
    gate = Gate(trace_dir if trace else None)
    try:
        client = fc._await_stamp(daemon, spool)
        # ---- set-up: two primer jobs take both workers ...
        primer = Runner("primer", ctx, client, suite, options, gate)
        spec0 = primer.edit(0)
        for item in suite[:2]:
            job = primer.post("edit", 0, spec0, [item])[0]
            primer.warm.append(job)
            need(job["code"] == 200, f"primer job refused: {job['error']}")
            primer.await_running(job)
        # ... and every runner's warm-up commit stands whole behind them
        runners = [Runner(tenant, ctx, client, suite, options, gate)
                   for tenant in mix["runners"]]
        for r in runners:
            r.warm = r.post("edit", 0, r.edit(0))
        for r in runners:
            r.start()
        primer.await_verdicts(primer.warm, 0)
        code, stt = client.call("GET", "/status")
        owner_pid = stt.get("device_owner_pid") if code == 200 else None
        kids = st._children(daemon.pid)  # a killed daemon stops none
        for job in primer.warm:
            need(job["status"] == "done" and job["art"],
                 f"the owner did not come up: primer job {job['label']} "
                 f"ended {job['status']!r}: "
                 f"{(job.get('rec') or {}).get('error')}")
            got = (job["art"].get("env") or {}).get("platform")
            need(got == platform,
                 f"the owner runs on {got!r}, not {platform!r}")
        # ---- the window: opened by the first runner through the gate
        for r in runners:
            r.join()
        need(daemon.poll() is None,
             f"the daemon died in the window (rc {daemon.returncode})")
        for r in runners:
            if r.error is not None:
                raise r.error if isinstance(r.error, BenchFailure) else \
                    BenchFailure(f"runner {r.tenant}: "
                                 f"{type(r.error).__name__}: {r.error}")
        if trace:
            fc._touch(trace_dir, "stop")
            fc._wait_for(trace_dir, "stopped", 300)
        code, stt = client.call("GET", "/status")
        need(code == 200, f"/status answered {code}")
        clean = True
    finally:
        rc = st.stop_all(daemon, [owner_pid] + kids, log, clean)
        shutil.rmtree(os.path.join(spool, "ckpt"), ignore_errors=True)
    warm_jobs = primer.warm + [j for r in runners for j in r.warm]
    jobs = [j for r in runners for j in r.jobs]
    say(f"bench: daemon drained, rc {rc}; {len(jobs)} job(s) in the window, "
        f"{sum(r.commits for r in runners)} commit(s), "
        f"{len(runners)} runner(s); {len(warm_jobs)} job(s) in set-up")

    # ---- no result at all where a job was not on the device
    for job in warm_jobs + jobs:
        art = job.get("art")
        if job["status"] != "done" or not art:
            continue
        envb, resb = art.get("env") or {}, art.get("result") or {}
        need(envb.get("platform") == platform,
             f"a job ran on {envb.get('platform')!r}, not {platform!r}")
        need(resb.get("finished_on") == "jax",
             f"a job finished on {resb.get('finished_on')!r}")
        need(not (art.get("gauges") or {}).get("device.demoted"),
             "a job DEMOTED off the device")

    # ---- correct: every verdict beside the plain reference, after the
    # window; then what the configuration guarantees of the service
    refs = {}
    for it in suite:
        refs[it["label"]] = reference_answer(mix, it["cfg_text"],
                                             ctx["bench_dir"])
        if not rehearsal:
            check_pins(refs[it["label"]], it["pins"])
    counters = stt.get("counters") or {}
    owner_on = bool(stt.get("device_owner_pid")) and \
        stt.get("daemon_holds_device") is False
    warm_ok = all([_judge(j, refs, owner_on) for j in warm_jobs])
    verdicts = [_judge(j, refs, owner_on) for j in jobs]
    failed = verdicts.count(False)
    ids = [j["id"] for j in warm_jobs + jobs if j.get("id")]
    service = [
        ("one verdict a submission", len(set(ids)),
         len(warm_jobs) + len(jobs)),
        ("daemon_holds_device", stt.get("daemon_holds_device"), False),
        ("device_owner_pid set", bool(stt.get("device_owner_pid")), True),
        ("device_owner_pid", stt.get("device_owner_pid"), owner_pid),
        ("serve.owner_respawns",
         counters.get("serve.owner_respawns", 0), 0),
        ("serve.admission_rejected (429)",
         counters.get("serve.admission_rejected", 0), 0),
        ("serve.batch_incompatible",
         counters.get("serve.batch_incompatible", 0), 0),
        ("serve.batch_solo_retries",
         counters.get("serve.batch_solo_retries", 0), 0),
        ("quarantined", stt.get("quarantined", 0), 0),
        ("jobs_failed", stt.get("jobs_failed", 0), 0),
        ("serve.jobs_done", counters.get("serve.jobs_done", 0), len(ids)),
        ("daemon drained cleanly (rc)", rc, 0),
    ]
    service_ok = all([st._check(*row) for row in service])

    window = [st._summary(j) for j in jobs]
    searched = [j for j in window if j["status"] == "done"]
    wall = max(j["t_result"] for j in window) - \
        min(j["t_post"] for j in window)
    rate = sum(j["result"].get("generated") or 0 for j in searched) / wall
    cohorts = _cohorts(searched)
    last = [round(j["client_s"], 2) for j in searched
            if j["label"] == suite[-1]["label"]]
    say(f"bench: {len(searched)} searched job(s) of {len(cohorts)} "
        f"commit(s) in {wall:.3f}s{' (traced)' if trace else ''}; "
        f"client walls of the matrix's last cfg {last}")
    for row in cohorts:
        whole = row["occupancy"] == [len(suite)] * len(suite)
        say(f"  cohort {row['runner']}#{row['commit']}: {row['jobs']} "
            f"job(s), batch_occupancy {row['occupancy']}, dispatches "
            f"{row['dispatches']}"
            f"{'' if whole else '  <-- NOT one cohort of the matrix'}")
    every = [st._summary(j) for j in warm_jobs] + window
    env0 = every[0]["env"]
    with open(os.path.join(work, "window.json"), "w",
              encoding="utf-8") as fh:   # for looking at a run by hand
        json.dump({"jobs": every, "status": stt, "wall_s": wall,
                   "cohorts": cohorts}, fh)

    return {
        "attempted": len(jobs), "failed": failed,
        "correct": bool(warm_ok and failed == 0 and service_ok),
        "values": {"states_per_s": rate,
                   "setup_s": gate.t_window - ctx["t0"]},
        "device": {"platform": env0.get("platform"),
                   "kind": env0.get("device_kind"),
                   "count": env0.get("device_count"),
                   "memory_peak_bytes": int(max(j["peak_bytes"]
                                                for j in every))},
        "trace_dir": trace_dir if trace else None,
        "artifacts": {"jobs": window, "warmup": every[:len(warm_jobs)],
                      "status": stt, "window_wall_s": wall,
                      "at_window": _totals([]), "after": _totals(searched),
                      "searches": len(searched), "cohorts": cohorts,
                      "commits": len(cohorts),
                      "reference": refs[suite[-1]["label"]]},
    }
