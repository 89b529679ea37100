"""Driver for the `firstcontact` kind of traffic: ONE served job, timed by the
client.  This process never imports jax: the daemon's device-owner child
must get the chip.

Set-up (counted in `setup_s`): an EMPTY compile cache and spool under
.bench_work/<cell>/, the daemon started as the configuration says, its
`serve.json` stamp awaited, and the owner brought up with one unrelated toy
job.  Window: submit the seed's copy of the spec with the seed's cfg for the
first time, poll for the verdict, read the result artifact back.  The run
ends at the verdict; `--seconds` does not cut it short.

With `--trace 1` the daemon is started through `traced_daemon.py`, the same
`jaxmc.serve` entry with a thread added that lets THIS process switch the
profiler on and off inside the owner (the only process that can trace the
chip), and the verdict is followed by byte-identical resubmissions for
`rerun_verdict_s`.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import subprocess
import sys
import time

from lib import (BenchFailure, check_pins, child_env, compare, need,
                 reference_answer, say, stamp_spec, work_dir, write_seed_cfg)

HERE = os.path.dirname(os.path.abspath(__file__))


class Client:
    """The HTTP protocol of jaxmc.serve, from its README: POST /jobs,
    GET /jobs/<id>, GET /jobs/<id>/result, GET /status."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port

    def call(self, method: str, path: str, body=None):
        conn = http.client.HTTPConnection(self.host, self.port, timeout=30)
        try:
            data = None if body is None else json.dumps(body).encode()
            conn.request(method, path, body=data,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            raw = resp.read().decode()
            try:
                return resp.status, json.loads(raw)
            except ValueError:
                return resp.status, {"error": raw[:200]}
        finally:
            conn.close()

    def run_job(self, spec, cfg, options, timeout_s, poll_s):
        """(seconds, job record, result artifact) of one job, on the
        client's clock from the POST sent to the artifact read back."""
        t0 = time.perf_counter()
        code, job = self.call("POST", "/jobs", {"spec": spec, "cfg": cfg,
                                                "options": options})
        if code != 200:
            return time.perf_counter() - t0, {"status": f"refused:{code}",
                                              "error": job}, None
        rec = {}
        while time.perf_counter() - t0 < timeout_s:
            code, rec = self.call("GET", f"/jobs/{job['id']}")
            if code == 200 and rec.get("status") in (
                    "done", "failed", "drained", "quarantined"):
                break
            time.sleep(poll_s)
        else:
            return time.perf_counter() - t0, {"status": "timeout"}, None
        art = None
        if rec.get("status") == "done":
            code, art = self.call("GET", f"/jobs/{job['id']}/result")
            art = art if code == 200 else None
        return time.perf_counter() - t0, rec, art


def _await_stamp(daemon, spool, timeout_s=90.0):
    stamp = os.path.join(spool, "serve.json")
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        need(daemon.poll() is None,
             f"daemon died at start-up (rc {daemon.returncode})")
        try:
            with open(stamp, encoding="utf-8") as fh:
                info = json.load(fh)
            if info.get("status") == "serving" and \
                    info.get("pid") == daemon.pid:
                return Client(info["host"], info["port"])
        except (OSError, ValueError):
            pass
        time.sleep(0.05)
    raise BenchFailure(f"daemon did not stamp {stamp} in {timeout_s:.0f}s")


def _stop(daemon, owner_pid, log):
    """SIGTERM -> clean drain; every process this run started has ended
    before the run returns."""
    if daemon.poll() is None:
        daemon.send_signal(signal.SIGTERM)
        try:
            daemon.wait(timeout=60)
        except subprocess.TimeoutExpired:
            daemon.kill()
            daemon.wait()
    if owner_pid:
        for _ in range(200):
            try:
                os.kill(owner_pid, 0)
            except OSError:
                break
            time.sleep(0.05)
        else:
            try:
                os.kill(owner_pid, signal.SIGKILL)
            except OSError:
                pass
    log.close()
    return daemon.returncode


def _job_answer(art) -> dict:
    res = (art or {}).get("result") or {}
    return {"generated": res.get("generated"),
            "distinct": res.get("distinct"),
            "diameter": res.get("diameter"), "ok": res.get("ok"),
            "truncated": res.get("truncated")}


def run(ctx: dict) -> dict:
    mix, pins, conf, root = ctx["mix"], ctx["pins"], ctx["config"], \
        ctx["root"]
    rehearsal, trace = ctx["rehearsal"], ctx["trace"]
    platform = "cpu" if rehearsal else conf["session"]["platform"]
    work = work_dir(ctx["cell"]["name"], root)
    spool, cache = os.path.join(work, "spool"), os.path.join(work, "cache")
    os.makedirs(cache)
    cfg_text, cfg_path = write_seed_cfg(ctx, work)
    spec_path = os.path.join(work, os.path.basename(mix["spec"]))
    with open(spec_path, "w", encoding="utf-8") as fh:
        fh.write(stamp_spec(open(os.path.join(root, mix["spec"]),
                                 encoding="utf-8").read(), ctx["seed"]))
    base = {"backend": conf["session"]["backend"], "platform": platform}
    options = dict(base, **mix["job_options"])
    if mix.get("use_pinned_caps") and not rehearsal:
        options["res_caps"] = dict(pins["res_caps"])

    env = child_env(root, JAX_COMPILATION_CACHE_DIR=cache)
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
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
    owner_pid = None
    try:
        client = _await_stamp(daemon, spool)
        wj = mix["warmup_job"]
        _, rec, art = client.run_job(
            os.path.join(root, wj["spec"]), os.path.join(root, wj["cfg"]),
            dict(base, **wj["options"]), mix["job_timeout_s"],
            mix["poll_s"])
        need(rec.get("status") == "done" and art,
             f"the owner did not come up: toy job ended "
             f"{rec.get('status')!r}: {rec.get('error')}")
        need((art.get("env") or {}).get("platform") == platform,
             f"the owner runs on {(art.get('env') or {}).get('platform')!r},"
             f" not {platform!r}")
        code, st = client.call("GET", "/status")
        owner_pid = st.get("device_owner_pid") if code == 200 else None

        # ---- the window: one operation
        if trace:
            _touch(trace_dir, "start")
            _wait_for(trace_dir, "started", 30)
        setup_s = time.time() - ctx["t0"]
        first_s, rec, art = client.run_job(spec_path, cfg_path, options,
                                           mix["job_timeout_s"],
                                           mix["poll_s"])
        if trace:
            _touch(trace_dir, "stop")
            _wait_for(trace_dir, "stopped", 120)
        rerun_s = []
        if trace and rec.get("status") == "done":
            for _ in range(mix["reruns"]):
                dt, r2, a2 = client.run_job(spec_path, cfg_path, options,
                                            30, 0.002)
                if r2.get("status") == "done" and a2 and \
                        _job_answer(a2) == _job_answer(art):
                    rerun_s.append(dt)
        code, st = client.call("GET", "/status")
        need(code == 200, f"/status answered {code}")
    finally:
        rc = _stop(daemon, owner_pid, log)
    say(f"bench: daemon drained, rc {rc}; job ended "
        f"{rec.get('status')!r} after {first_s:.3f}s")

    # ---- no result at all where the run was not on the device
    job_ok = rec.get("status") == "done" and art is not None
    if job_ok:
        envb, resb = art.get("env") or {}, art.get("result") or {}
        need(envb.get("platform") == platform,
             f"the job ran on {envb.get('platform')!r}, not {platform!r}")
        need(resb.get("finished_on") == "jax",
             f"the job finished on {resb.get('finished_on')!r}")
        need(not (art.get("gauges") or {}).get("device.demoted"),
             "the job DEMOTED off the device")
    # ---- correct
    ref = reference_answer(mix, cfg_text, ctx["bench_dir"])
    if not rehearsal:
        check_pins(ref, pins)
    good = job_ok and compare(_job_answer(art), ref, "job")
    checks = [
        ("serve.warm_engine", bool(((art or {}).get("serve") or {})
                                   .get("warm_engine")), False),
        ("daemon_holds_device", st.get("daemon_holds_device"), False),
        ("owner_respawns",
         (st.get("counters") or {}).get("serve.owner_respawns", 0), 0),
        ("device_owner_pid set", bool(st.get("device_owner_pid")), True),
        ("daemon drained cleanly (rc)", rc, 0),
    ]
    for label, got, want in checks:
        say(f"  compare {label}: program {got} wanted {want} "
            f"{'ok' if got == want else 'FAILED'}")
        good = good and got == want
    envb = (art or {}).get("env") or {}
    peak = (((art or {}).get("prof") or {}).get("hbm") or {}).get(
        "measured_peak_bytes") or 0
    return {
        "attempted": 1, "failed": 0 if good else 1, "correct": bool(good),
        "values": {"firstcontact_s": first_s, "setup_s": setup_s},
        "device": {"platform": envb.get("platform"),
                   "kind": envb.get("device_kind"),
                   "count": envb.get("device_count"),
                   "memory_peak_bytes": int(peak)},
        "trace_dir": trace_dir if trace else None,
        "artifacts": {"job": art or {}, "status": st, "rerun_s": rerun_s,
                      "reference": ref},
    }


def _touch(d, name):
    with open(os.path.join(d, name), "w"):
        pass


def _wait_for(d, name, timeout_s):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if os.path.exists(os.path.join(d, name)):
            return
        time.sleep(0.01)
    raise BenchFailure(f"the owner's trace hook did not answer {name!r} "
                       f"in {timeout_s}s")
