"""Driver for the `stream` kind of traffic: commit replay against ONE served
daemon, timed by its clients.  This process never imports jax or jaxmc: the
daemon's device-owner child must get the chip, and the clients speak the
HTTP protocol with the standard library alone.

The mix names the runners (tenants), the suite (the cfgs every commit
checks), and the cycle every runner repeats (`edit` ... `rerun`).  A runner
is a closed loop with ONE commit in flight: it POSTs the commit's suite back
to back, polls every `poll_s` until every verdict is read back through
GET /jobs/<id>/result, then goes on.  An `edit` is a fresh `lib.stamp_spec`
copy of the spec (a stamp of its own for every seed, runner and commit
index: a new content hash, so a new signature, a full build and a full
search); a `rerun` resubmits the previous edit's jobs byte for byte (warm
engine, replay of the finalized checkpoint).  A runner starts cycles until
`--seconds` have passed and finishes the cycle it is in, so every window
holds whole cycles and the same composition.

Set-up (counted in `setup_s`): the spool under .bench_work/<cell>/, the
daemon started as the configuration says (the checkout's compile cache as
jaxmc resolves it), its `serve.json` stamp awaited, and one warm-up commit
(the whole suite, a stamp of its own) that brings the owner, the chip and
every program up.  `states_per_s` = the `generated` of the window's jobs
that SEARCHED (a replay adds a job, never a state) over the clients' wall
from the first POST to the last result read back.

With `--trace 1` the daemon is started through `traced_daemon.py` (the same
`jaxmc.serve` entry plus the thread through which this process switches the
profiler on inside the owner); the window is the same.  The daemon and its owner are stopped at the end of every run, on failure
too, and the spool's checkpoints are removed.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

from lib import (BenchFailure, check_pins, child_env, compare, load_json,
                 load_module, need, permute_cfg, reference_answer, say,
                 stamp_spec, work_dir)

HERE = os.path.dirname(os.path.abspath(__file__))
fc = load_module(os.path.join(HERE, "firstcontact.py"),
                 "bench_driver_firstcontact")

ENDED = ("done", "failed", "drained", "quarantined")


# ------------------------------------------------------------ processes

def _children(pid: int) -> list:
    """Pids whose parent is `pid` (/proc; the owner of a daemon that never
    answered /status)."""
    out = []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat", encoding="utf-8") as fh:
                stat = fh.read()
            if int(stat.rsplit(")", 1)[1].split()[1]) == pid:
                out.append(int(name))
        except (OSError, ValueError, IndexError):
            pass
    return out


def _alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except OSError:
        return False
    try:  # a zombie is not alive
        with open(f"/proc/{pid}/stat", encoding="utf-8") as fh:
            return fh.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def stop_all(daemon, owner_pids, log, clean: bool) -> int:
    """Every process this run started has ended when this returns: a clean
    drain where the run went well, a kill where it did not."""
    kids = set(p for p in owner_pids if p)
    if daemon.poll() is None:
        kids.update(_children(daemon.pid))
        if not clean:
            daemon.kill()
            daemon.wait()
    rc = fc._stop(daemon, None, log)
    for pid in kids:
        # a drained daemon has stopped its owner; a killed one has not
        deadline = time.time() + (10.0 if clean else 0.0)
        while _alive(pid) and time.time() < deadline:
            time.sleep(0.05)
        if _alive(pid):
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        deadline = time.time() + 5.0
        while _alive(pid) and time.time() < deadline:
            time.sleep(0.05)
        need(not _alive(pid), f"process {pid} outlived the run")
    return rc


# -------------------------------------------------------------- clients

class Runner(threading.Thread):
    """One CI runner: a tenant, a closed loop, one commit in flight."""

    def __init__(self, tenant, ctx, client, suite, options, stop_at):
        super().__init__(name=f"bench-runner-{tenant}", daemon=True)
        self.tenant, self.ctx = tenant, ctx
        self.client, self.suite, self.options = client, suite, options
        self.stop_at = stop_at
        self.jobs, self.error, self.commits = [], None, 0

    def commit_dir(self, k: int) -> str:
        return os.path.join(self.ctx["work"], "commits",
                            f"{self.tenant}-{k:04d}")

    def edit(self, k: int) -> str:
        """The k-th edited commit of this runner: a copy of the spec under a
        directory of its own (the module keeps its file name), stamped from
        the seed, the runner and k."""
        d = self.commit_dir(k)
        os.makedirs(d)
        path = os.path.join(d, os.path.basename(self.ctx["mix"]["spec"]))
        with open(path, "w", encoding="utf-8") as fh:
            fh.write(stamp_spec(
                self.ctx["spec_text"],
                f"{self.ctx['seed']} runner {self.tenant} commit {k}"))
        return path

    def commit(self, kind: str, k: int, spec_path: str) -> None:
        mix = self.ctx["mix"]
        inflight = []
        for item in self.suite:
            job = {"runner": self.tenant, "commit": k, "kind": kind,
                   "label": item["label"], "t_post": time.time()}
            code, body = self.client.call("POST", "/jobs", {
                "spec": spec_path, "cfg": item["cfg_path"],
                "options": self.options[item["label"]],
                "tenant": self.tenant})
            job.update(t_posted=time.time(), code=code,
                       id=body.get("id") if code == 200 else None,
                       sig=body.get("sig") if code == 200 else None,
                       status="posted" if code == 200
                       else f"refused:{code}", error=None if code == 200
                       else body, art=None, rec={})
            job["t_result"] = job["t_posted"]  # until a verdict is read
            self.jobs.append(job)
            if code == 200:
                inflight.append(job)
        deadline = time.time() + mix["job_timeout_s"]
        while inflight and time.time() < deadline:
            for job in list(inflight):
                code, rec = self.client.call("GET", f"/jobs/{job['id']}")
                if code == 200 and rec.get("status") in ENDED:
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
            k, last = 0, None
            while True:
                for step in self.ctx["mix"]["cycle"]:
                    if step == "edit":
                        k += 1
                        last = self.edit(k)
                    self.commit(step, k, last)
                    self.commits += 1
                if time.time() >= self.stop_at:
                    break
        except Exception as ex:  # noqa: BLE001 — reported by run()
            self.error = ex


# ------------------------------------------------------------- the run

def _suite(ctx, work):
    """The commit's suite as this seed writes it: [{label, cfg_path,
    cfg_text, pins}] (at toy size in a rehearsal)."""
    mix, root = ctx["mix"], ctx["root"]
    out = []
    for i, item in enumerate(mix["suite"]):
        src = mix["rehearsal_suite"][i] if ctx["rehearsal"] else \
            open(os.path.join(root, item["cfg"]), encoding="utf-8").read()
        text = permute_cfg(src, ctx["seed"])
        path = os.path.join(work, os.path.basename(item["cfg"]))
        with open(path, "w", encoding="utf-8") as fh:
            fh.write(text)
        out.append({"label": item["label"], "cfg_path": path,
                    "cfg_text": text,
                    "pins": load_json(os.path.join(
                        ctx["bench_dir"], "pins", item["pins"] + ".json"))})
    return out


def _summary(job) -> dict:
    """What the per-layer readers need of one job, without its levels."""
    art, rec = job.get("art") or {}, job.get("rec") or {}
    prof = art.get("prof") or {}
    return {
        "runner": job["runner"], "commit": job["commit"],
        "kind": job["kind"], "label": job["label"], "id": job.get("id"),
        "status": job["status"], "client_s": job["t_result"] - job["t_post"],
        "t_post": job["t_post"], "t_posted": job.get("t_posted"),
        "t_seen": job.get("t_seen"), "t_result": job["t_result"],
        # the record's own clock (serve/protocol.py)
        "submitted_at": rec.get("submitted_at"),
        "started_at": rec.get("started_at"),
        "finished_at": rec.get("finished_at"),
        "serve": art.get("serve") or {},
        "result": art.get("result") or {},
        "phases": {p["name"]: p["wall_s"] for p in art.get("phases", [])},
        "counters": art.get("counters") or {},
        "gauges": {k: v for k, v in (art.get("gauges") or {}).items()
                   if k.startswith("program.")},
        "dispatches": {n: s.get("dispatches", 0)
                       for n, s in (prof.get("sites") or {}).items()},
        "compiled": sum(1 for p in prof.get("programs", [])
                        if p.get("origin") == "compiled"),
        "peak_bytes": (prof.get("hbm") or {}).get("peak_bytes") or 0,
        "env": art.get("env") or {},
        "demoted": (art.get("gauges") or {}).get("device.demoted"),
    }


def _check(label, got, want) -> bool:
    say(f"  compare {label}: program {got} wanted {want} "
        f"{'ok' if got == want else 'FAILED'}")
    return got == want


def _judge(job, refs) -> bool:
    """One job's verdict beside the plain reference's for its cfg, and
    beside what the configuration guarantees of the way it was answered."""
    tag = f"{job['runner']}#{job['commit']}.{job['kind']}.{job['label']}"
    if job["status"] != "done" or not job.get("art"):
        say(f"  compare {tag}: ended {job['status']!r} "
            f"{job.get('error') or (job.get('rec') or {}).get('error')} "
            f"FAILED")
        return False
    art = job["art"]
    good = compare(fc._job_answer(art), refs[job["label"]], tag)
    sv = art.get("serve") or {}
    rerun = job["kind"] == "rerun"
    for name, got, want in (
            ("warm_engine", bool(sv.get("warm_engine")), rerun),
            ("resumed_from_checkpoint",
             bool(sv.get("resumed_from_checkpoint")), rerun),
            ("device_owner", bool(sv.get("device_owner")), True)):
        good = _check(f"{tag} {name}", got, want) and good
    return good


def run(ctx: dict) -> dict:
    mix, conf, root = ctx["mix"], ctx["config"], ctx["root"]
    rehearsal, trace = ctx["rehearsal"], ctx["trace"]
    platform = "cpu" if rehearsal else conf["session"]["platform"]
    work = work_dir(ctx["cell"]["name"], root)
    spool = os.path.join(work, "spool")
    suite = _suite(ctx, work)
    ctx = dict(ctx, work=work, spec_text=open(
        os.path.join(root, mix["spec"]), encoding="utf-8").read())
    base = dict(mix["job_options"], backend=conf["session"]["backend"],
                platform=platform)
    options = {it["label"]: dict(base, **(
        {"res_caps": dict(it["pins"]["res_caps"])}
        if mix.get("use_pinned_caps") and not rehearsal else {}))
        for it in suite}

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
    owner_pid, kids, clean, runners = None, [], False, []
    try:
        client = fc._await_stamp(daemon, spool)
        # ---- warm-up: one commit of its own, the whole suite
        warm = Runner("warmup", ctx, client, suite, options, 0)
        warm.commit("edit", 0, warm.edit(0))
        code, st = client.call("GET", "/status")
        owner_pid = st.get("device_owner_pid") if code == 200 else None
        kids = _children(daemon.pid)  # a killed daemon stops none of them
        for job in warm.jobs:
            need(job["status"] == "done" and job["art"],
                 f"the owner did not come up: warm-up job "
                 f"{job['label']} ended {job['status']!r}: "
                 f"{(job.get('rec') or {}).get('error')}")
            got = (job["art"].get("env") or {}).get("platform")
            need(got == platform,
                 f"the owner runs on {got!r}, not {platform!r}")

        # ---- the window
        if trace:
            fc._touch(trace_dir, "start")
            fc._wait_for(trace_dir, "started", 30)
        t_window = time.time()
        setup_s = t_window - ctx["t0"]
        runners = [Runner(tenant, ctx, client, suite, options,
                          t_window + ctx["seconds"])
                   for tenant in mix["runners"]]
        for r in runners:
            r.start()
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
        code, st = client.call("GET", "/status")
        need(code == 200, f"/status answered {code}")
        clean = True
    finally:
        rc = stop_all(daemon, [owner_pid] + kids, log, clean)
        shutil.rmtree(os.path.join(spool, "ckpt"), ignore_errors=True)
    jobs = [j for r in runners for j in r.jobs]
    say(f"bench: daemon drained, rc {rc}; {len(jobs)} job(s) in the window, "
        f"{sum(r.commits for r in runners)} commit(s), "
        f"{len(runners)} runner(s)")

    # ---- no result at all where a job was not on the device
    for job in warm.jobs + jobs:
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
    warm_ok = all([_judge(j, refs) for j in warm.jobs])
    verdicts = [_judge(j, refs) for j in jobs]
    failed = verdicts.count(False)
    counters = st.get("counters") or {}
    ids = [j["id"] for j in warm.jobs + jobs if j.get("id")]
    service = [
        ("one verdict a submission", len(set(ids)),
         len(warm.jobs) + len(jobs)),
        ("daemon_holds_device", st.get("daemon_holds_device"), False),
        ("device_owner_pid set", bool(st.get("device_owner_pid")), True),
        ("device_owner_pid", st.get("device_owner_pid"), owner_pid),
        ("serve.owner_respawns",
         counters.get("serve.owner_respawns", 0), 0),
        ("serve.admission_rejected (429)",
         counters.get("serve.admission_rejected", 0), 0),
        ("quarantined", st.get("quarantined", 0), 0),
        ("jobs_failed", st.get("jobs_failed", 0), 0),
        ("serve.jobs_done", counters.get("serve.jobs_done", 0), len(ids)),
        ("daemon drained cleanly (rc)", rc, 0),
    ]
    service_ok = all([_check(*row) for row in service])

    window = [_summary(j) for j in jobs]
    searched = [j for j in window if j["kind"] == "edit"
                and j["status"] == "done"]
    replays = [j for j in window if j["kind"] == "rerun"]
    wall = max(j["t_result"] for j in window) - \
        min(j["t_post"] for j in window)
    rate = sum(j["result"].get("generated") or 0 for j in searched) / wall
    say(f"bench: {len(searched)} searched job(s) and {len(replays)} "
        f"replay(s) in {wall:.3f}s"
        f"{' (traced)' if trace else ''}; searched-job client walls "
        f"{[round(j['client_s'], 2) for j in searched]}")
    every = [_summary(j) for j in warm.jobs] + window
    env0 = every[0]["env"]
    with open(os.path.join(work, "window.json"), "w",
              encoding="utf-8") as fh:   # for looking at a run by hand
        json.dump({"jobs": every, "status": st, "wall_s": wall}, fh)

    def totals(js):
        # the desk cells' shape (`at_window` / `after`), so that the
        # accepted readers read this cell's jobs as they read a session's
        # searches: every job's recorder starts at zero, so the window's
        # rise is the sum over its searched jobs — dispatches by site,
        # counters, and the largest program's gauges
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

    return {
        "attempted": len(jobs), "failed": failed,
        "correct": bool(warm_ok and failed == 0 and service_ok),
        "values": {"states_per_s": rate, "setup_s": setup_s},
        "device": {"platform": env0.get("platform"),
                   "kind": env0.get("device_kind"),
                   "count": env0.get("device_count"),
                   "memory_peak_bytes": int(max(j["peak_bytes"]
                                                for j in every))},
        "trace_dir": trace_dir if trace else None,
        "artifacts": {"jobs": window, "warmup": every[:len(warm.jobs)],
                      "status": st, "window_wall_s": wall,
                      "at_window": totals([]), "after": totals(searched),
                      "searches": len(searched),
                      "reference": refs[suite[-1]["label"]]},
    }
