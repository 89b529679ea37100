r"""Fault-injection chaos suite (ISSUE 4) — `make chaos` runs `-m chaos`.

End-to-end proof that jaxmc survives the failures long runs actually
hit, driven by the deterministic JAXMC_FAULTS registry (jaxmc/faults.py):

- a SIGKILLed pool worker: the chunk is requeued, the pool respawned,
  and state counts stay BYTE-IDENTICAL to the serial engine (the ISSUE 4
  acceptance run: worker_kill:level=2, --workers 4, specs/viewtoy.tla);
- exhausted retries degrade to serial expansion with `parallel.degraded`
  telemetry — and still-exact counts;
- a corrupted checkpoint is refused (exit 2), never half-resumed;
- device init failures retry; a terminal device failure demotes to the
  parallel CPU engine RESUMING from the host snapshot;
- SIGKILL of the whole run mid-level (serial / parallel / device):
  resume from the checkpoint reproduces the uninterrupted run's counts
  bit-identically (marked slow — kept out of tier-1 timing).
"""

import json
import multiprocessing
import os
import subprocess
import sys

import pytest

from jaxmc import faults, obs
from jaxmc.front.cfg import parse_cfg
from jaxmc.sem.modules import Loader, bind_model
from jaxmc.engine.explore import Explorer
from jaxmc.engine.parallel import ParallelExplorer, fork_available

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = os.path.join(REPO, "specs")

needs_fork = pytest.mark.skipif(not fork_available(),
                                reason="no fork start method")

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _fresh_faults(monkeypatch):
    monkeypatch.delenv("JAXMC_FAULTS", raising=False)
    monkeypatch.delenv("JAXMC_FAULTS_STATE", raising=False)
    faults._CACHE = None
    yield
    faults._CACHE = None


def load(spec, cfg=None):
    cfgp = cfg or os.path.splitext(spec)[0] + ".cfg"
    with open(cfgp) as fh:
        c = parse_cfg(fh.read())
    return bind_model(Loader([SPECS]).load_path(spec), c)


def _cli(args, env_extra=None, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("JAXMC_FAULTS", None) if env_extra is None else None
    return subprocess.run([sys.executable, "-m", "jaxmc", "check"] + args,
                          capture_output=True, text=True, cwd=REPO,
                          env=env, timeout=timeout)


def _counts(stdout):
    """(generated, distinct) from the CLI summary line."""
    for line in stdout.splitlines():
        if "states generated," in line and "distinct states found" in \
                line and "states/sec" in line:
            parts = line.split()
            return int(parts[0]), int(parts[3])
    raise AssertionError(f"no summary line in:\n{stdout}")


# ------------------------------------------------ parallel crash safety

@needs_fork
class TestWorkerCrash:
    def test_worker_kill_requeue_parity_acceptance(self, monkeypatch):
        # THE ISSUE 4 acceptance scenario, in-process: with
        # JAXMC_FAULTS=worker_kill:level=2 a --workers 4 run on
        # specs/viewtoy.tla completes with counts byte-identical to the
        # serial engine, and telemetry records the requeue/respawn
        rs = Explorer(load(os.path.join(SPECS, "viewtoy.tla"))).run()
        monkeypatch.setenv("JAXMC_FAULTS", "worker_kill:level=2")
        faults._CACHE = None
        tel = obs.Telemetry()
        with obs.use(tel):
            rp = ParallelExplorer(load(os.path.join(SPECS,
                                                    "viewtoy.tla")),
                                  workers=4).run()
        assert (rp.generated, rp.distinct, rp.diameter) == \
            (rs.generated, rs.distinct, rs.diameter)
        assert rp.ok == rs.ok
        assert tel.counters.get("parallel.worker_deaths") == 1
        assert tel.counters.get("parallel.respawns") == 1
        assert tel.counters.get("parallel.requeues", 0) >= 1
        # (faults.injected is counted in the KILLED worker's memory —
        # the parent-side proof of the firing is the worker_death above)
        # recovered, NOT degraded: the pool finished the run
        assert tel.gauges.get("parallel.degraded") is None

    def test_worker_kill_acceptance_via_cli(self, tmp_path):
        # the same scenario through the CLI (what the driver runs),
        # with the requeue/respawn telemetry in the metrics artifact
        spec = os.path.join(SPECS, "viewtoy.tla")
        r_serial = _cli([spec, "--workers", "1"], env_extra={})
        assert r_serial.returncode == 0, r_serial.stderr
        m = str(tmp_path / "m.json")
        r_par = _cli([spec, "--workers", "4", "--metrics-out", m],
                     env_extra={"JAXMC_FAULTS": "worker_kill:level=2"})
        assert r_par.returncode == 0, r_par.stderr
        assert _counts(r_par.stdout) == _counts(r_serial.stdout)
        art = json.load(open(m))
        assert art["counters"].get("parallel.worker_deaths") == 1
        assert art["counters"].get("parallel.respawns") == 1
        assert art["gauges"].get("parallel.degraded") is None

    def test_repeated_kills_exhaust_budget_and_degrade(self, monkeypatch):
        # every respawned worker dies on the same chunk -> after the
        # bounded retry budget the run degrades to serial expansion,
        # with the degradation recorded — and counts STILL exact
        rs = Explorer(load(os.path.join(SPECS, "viewtoy.tla"))).run()
        monkeypatch.setenv("JAXMC_FAULTS", "worker_kill:level=1:n=99")
        faults._CACHE = None
        tel = obs.Telemetry()
        with obs.use(tel):
            rp = ParallelExplorer(load(os.path.join(SPECS,
                                                    "viewtoy.tla")),
                                  workers=2).run()
        assert (rp.generated, rp.distinct) == (rs.generated, rs.distinct)
        assert tel.gauges.get("parallel.degraded")
        assert "retry budget exhausted" in tel.gauges["parallel.degraded"]
        assert tel.counters.get("parallel.degradations") == 1

    def test_transient_chunk_error_retried_inline(self, monkeypatch):
        rs = Explorer(load(os.path.join(SPECS, "constoy.tla"))).run()
        monkeypatch.setenv("JAXMC_FAULTS", "chunk_error:level=1")
        faults._CACHE = None
        tel = obs.Telemetry()
        with obs.use(tel):
            rp = ParallelExplorer(load(os.path.join(SPECS,
                                                    "constoy.tla")),
                                  workers=2).run()
        assert (rp.generated, rp.distinct) == (rs.generated, rs.distinct)
        assert tel.counters.get("parallel.chunk_retries") == 1
        assert tel.gauges.get("parallel.degraded") is None

    def test_no_orphan_processes_after_crashy_run(self, monkeypatch):
        monkeypatch.setenv("JAXMC_FAULTS", "worker_kill:level=2")
        faults._CACHE = None
        ParallelExplorer(load(os.path.join(SPECS, "viewtoy.tla")),
                         workers=3).run()
        assert multiprocessing.active_children() == []


# --------------------------------------------------- checkpoint faults

class TestCheckpointCorruption:
    def test_ckpt_corrupt_fault_rejected_on_resume(self, tmp_path):
        # the harness corrupts every checkpoint write; the resume must
        # refuse with exit 2 + a one-line diagnosis (acceptance: never
        # a traceback, never a silently-wrong resume)
        ck = str(tmp_path / "c.ck")
        spec = os.path.join(SPECS, "constoy.tla")
        r1 = _cli([spec, "--max-states", "10", "--checkpoint", ck,
                   "--checkpoint-every", "0", "--quiet"],
                  env_extra={"JAXMC_FAULTS": "ckpt_corrupt:n=1000"})
        assert r1.returncode == 0, r1.stderr
        assert os.path.exists(ck)
        r2 = _cli([spec, "--resume", ck, "--quiet"], env_extra={})
        assert r2.returncode == 2
        assert "cannot resume" in r2.stderr
        assert "Traceback" not in r2.stderr

    def test_ckpt_corrupt_flip_mode(self, tmp_path, monkeypatch):
        from jaxmc.engine.ckpt import CkptError, write_checkpoint, \
            load_checkpoint
        monkeypatch.setenv("JAXMC_FAULTS", "ckpt_corrupt:mode=flip")
        monkeypatch.setenv("JAXMC_FAULTS_STATE",
                           str(tmp_path / "fstate"))
        os.makedirs(str(tmp_path / "fstate"))
        faults._CACHE = None
        p = str(tmp_path / "c.ck")
        write_checkpoint(p, "interp", {}, {"blob": b"z" * 4096})
        with pytest.raises(CkptError):
            load_checkpoint(p)


# ------------------------------------------------- device fault paths

class TestDeviceFaults:
    def test_device_init_fail_retries_then_succeeds(self, tmp_path):
        m = str(tmp_path / "m.json")
        r = _cli([os.path.join(SPECS, "constoy.tla"), "--backend", "jax",
                  "--quiet", "--metrics-out", m],
                 env_extra={"JAXMC_FAULTS": "device_init_fail:n=2"})
        assert r.returncode == 0, r.stderr
        art = json.load(open(m))
        assert art["counters"].get("device.init_retries") == 2
        assert art["gauges"].get("device.demoted") is None

    def test_terminal_device_failure_demotes_with_snapshot(self,
                                                           tmp_path):
        # ISSUE 4 tentpole (4): on terminal device failure the run falls
        # back to the parallel CPU engine RESUMING from the last host
        # snapshot, completes with the interp's exact counts, and the
        # demotion is machine-readable (device.demoted — obs diff flags
        # its appearance)
        spec = os.path.join(SPECS, "constoy.tla")
        r_interp = _cli([spec], env_extra={})
        assert r_interp.returncode == 0
        ck = str(tmp_path / "c.ck")
        m = str(tmp_path / "m.json")
        r = _cli([spec, "--backend", "jax", "--checkpoint", ck,
                  "--checkpoint-every", "0", "--metrics-out", m],
                 env_extra={"JAXMC_FAULTS": "device_run_fail:level=2"})
        assert r.returncode == 0, r.stderr
        assert _counts(r.stdout) == _counts(r_interp.stdout)
        assert "falling back to the parallel CPU engine" in r.stderr
        assert "resuming from host snapshot" in r.stderr
        assert "completed on the parallel CPU engine" in r.stdout
        art = json.load(open(m))
        assert art["gauges"].get("device.demoted")
        assert art["counters"].get("device.demotions") == 1
        # obs diff raises a REGRESS flag when the demotion appears
        m_clean = str(tmp_path / "m0.json")
        r0 = _cli([spec, "--backend", "jax", "--quiet",
                   "--metrics-out", m_clean], env_extra={})
        assert r0.returncode == 0, r0.stderr
        d = subprocess.run(
            [sys.executable, "-m", "jaxmc.obs", "diff",
             "--fail-on-regress", "--threshold", "10000",
             m_clean, m], capture_output=True, text=True, cwd=REPO)
        assert d.returncode == 1
        assert "REGRESS device demotion" in d.stdout

    def test_no_device_fallback_flag_exits(self, tmp_path):
        r = _cli([os.path.join(SPECS, "constoy.tla"), "--backend", "jax",
                  "--no-device-fallback", "--quiet"],
                 env_extra={"JAXMC_FAULTS": "device_run_fail:level=1"})
        assert r.returncode == 2
        assert "injected fault: device_run_fail" in r.stderr


# --------------------------------------- kill/resume parity (satellite)

@pytest.mark.slow
class TestKillResumeParity:
    """SIGKILL a run mid-level, resume from the checkpoint, and pin the
    final counts + diameter bit-identical to an uninterrupted run —
    serial, parallel, and simulated-device (jax on CPU)."""

    def _kill_resume(self, extra_args, tmp_path, backend_tag):
        spec = os.path.join(SPECS, "constoy.tla")
        clean = _cli([spec] + extra_args, env_extra={})
        assert clean.returncode == 0, clean.stderr
        ck = str(tmp_path / f"{backend_tag}.ck")
        killed = _cli([spec] + extra_args +
                      ["--checkpoint", ck, "--checkpoint-every", "0",
                       "--quiet"],
                      env_extra={"JAXMC_FAULTS": "run_kill:level=3"})
        assert killed.returncode == -9 or killed.returncode == 137, \
            (killed.returncode, killed.stderr)
        assert os.path.exists(ck), "no checkpoint survived the kill"
        resumed = _cli([spec] + extra_args + ["--resume", ck],
                       env_extra={})
        assert resumed.returncode == 0, resumed.stderr
        assert _counts(resumed.stdout) == _counts(clean.stdout)
        # the depth line is printed by the engines on completion
        depth_clean = [ln for ln in clean.stdout.splitlines()
                       if "depth of the complete state graph" in ln]
        depth_res = [ln for ln in resumed.stdout.splitlines()
                     if "depth of the complete state graph" in ln]
        assert depth_res == depth_clean

    def test_serial_kill_resume(self, tmp_path):
        self._kill_resume(["--workers", "1"], tmp_path, "serial")

    @needs_fork
    def test_parallel_kill_resume(self, tmp_path):
        self._kill_resume(["--workers", "3"], tmp_path, "parallel")

    def test_device_kill_resume(self, tmp_path):
        self._kill_resume(["--backend", "jax"], tmp_path, "device")

    @needs_fork
    def test_parallel_resumes_serial_kill(self, tmp_path):
        # cross-engine: a checkpoint left by a SIGKILLed serial run
        # resumes on the parallel engine (no fallback) with exact counts
        spec = os.path.join(SPECS, "constoy.tla")
        clean = _cli([spec, "--workers", "1"], env_extra={})
        ck = str(tmp_path / "x.ck")
        _cli([spec, "--workers", "1", "--checkpoint", ck,
              "--checkpoint-every", "0", "--quiet"],
             env_extra={"JAXMC_FAULTS": "run_kill:level=3"})
        assert os.path.exists(ck)
        m = str(tmp_path / "m.json")
        resumed = _cli([spec, "--workers", "3", "--resume", ck,
                        "--metrics-out", m], env_extra={})
        assert resumed.returncode == 0, resumed.stderr
        assert _counts(resumed.stdout) == _counts(clean.stdout)
        art = json.load(open(m))
        assert art["gauges"].get("parallel.fallback_reason") is None
        assert art["gauges"].get("parallel.workers") == 3


# ------------------------------------------ trace-context chaos (ISSUE 16)

@needs_fork
class TestTraceContextChaos:
    """PR-16: the fleet trace survives the chaos matrix.  A worker that
    is SIGKILLed and respawned rejoins the run's ORIGINAL trace_id
    (fresh pid+span, same tid), and a SIGTERM-drained run plus its
    resume both stitch under the JAXMC_TRACE_CTX they inherited — in
    every case `obs timeline` reconstructs the fleet with zero orphan
    spans."""

    def test_worker_kill_respawn_keeps_trace_id(self, monkeypatch,
                                                tmp_path):
        import io
        from jaxmc.obs import context
        from jaxmc.obs.report import main as obs_main
        monkeypatch.setenv("JAXMC_FAULTS", "worker_kill:level=2")
        faults._CACHE = None
        context.reset()
        trace = str(tmp_path / "kill.trace.jsonl")
        tel = obs.Telemetry(trace_path=trace)
        with obs.use(tel):
            rp = ParallelExplorer(load(os.path.join(SPECS,
                                                    "viewtoy.tla")),
                                  workers=4).run()
        assert rp.ok
        assert tel.counters.get("parallel.worker_deaths") == 1
        assert tel.counters.get("parallel.respawns") == 1
        events = [json.loads(ln) for ln in open(trace)]
        run_tid = context.get().trace_id
        # one trace_id across the whole run — including every event
        # recorded AFTER the kill/respawn cycle
        assert {e.get("tid") for e in events} == {run_tid}
        spans = [e for e in events
                 if e.get("ev") == "parallel.worker_span"]
        # every worker (original or respawned) holds a DISTINCT
        # pid+span, all parented on the run's own span
        assert len(spans) >= 2, spans
        assert len({s["pid"] for s in spans}) == len(spans)
        assert len({s["span"] for s in spans}) == len(spans)
        assert all(s["parent"] == events[0]["psid"] for s in spans)
        buf = io.StringIO()
        rc = obs_main(["timeline", "--fail-on-orphans", trace],
                      out=buf)
        out = buf.getvalue()
        assert rc == 0, out
        assert "orphans=0" in out

    @pytest.mark.slow
    def test_sigterm_drain_and_resume_share_trace(self, tmp_path):
        # a SIGTERM-drained run checkpoints AND leaves a trace stitched
        # under the JAXMC_TRACE_CTX it inherited; the resume, handed
        # the same context, joins the SAME fleet trace — the conductor
        # lane plus both run lanes merge with zero orphans
        import io
        import signal
        import time
        from jaxmc.obs.report import main as obs_main
        from conftest import SLOW_CFG as _SLOW_CFG, SLOW_SPEC as _SLOW_SPEC

        spec = str(tmp_path / "traceload.tla")
        with open(spec, "w") as fh:
            fh.write(_SLOW_SPEC.format(q=800, bound=15))
        with open(str(tmp_path / "traceload.cfg"), "w") as fh:
            fh.write(_SLOW_CFG)
        parent_tid, parent_span = "ab" * 8, "cd" * 8
        env = {"JAXMC_TRACE_CTX": f"{parent_tid}:{parent_span}"}
        ck = str(tmp_path / "drain.ck")
        t1 = str(tmp_path / "one.trace.jsonl")
        proc = subprocess.Popen(
            [sys.executable, "-m", "jaxmc", "check", spec,
             "--workers", "1", "--trace", t1, "--checkpoint", ck,
             "--checkpoint-every", "0"],
            env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
            cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        deadline = time.time() + 120
        while not (os.path.exists(t1) and os.path.getsize(t1) > 0):
            assert proc.poll() is None, proc.communicate()[1]
            assert time.time() < deadline, "child never wrote a trace"
            time.sleep(0.1)
        proc.send_signal(signal.SIGTERM)
        _, err = proc.communicate(timeout=120)
        assert proc.returncode == 143, (proc.returncode, err)
        assert os.path.exists(ck), "the drain left no checkpoint"
        t2 = str(tmp_path / "two.trace.jsonl")
        resumed = _cli([spec, "--workers", "1", "--resume", ck,
                        "--trace", t2], env_extra=env)
        assert resumed.returncode == 0, resumed.stderr
        ev1 = [json.loads(ln) for ln in open(t1)]
        ev2 = [json.loads(ln) for ln in open(t2)]
        assert {e.get("tid") for e in ev1 + ev2} == {parent_tid}
        assert ev1[0]["parent_span"] == parent_span
        assert ev2[0]["parent_span"] == parent_span
        # a one-line conductor lane makes the inherited parent span
        # resolvable, exactly as a bench/serve parent's trace would
        parent_trace = str(tmp_path / "parent.trace.jsonl")
        with open(parent_trace, "w") as fh:
            fh.write(json.dumps({
                "ev": "proc_meta", "t": ev1[0]["t"] - 1.0, "mono": 0.0,
                "pid": 1, "argv": ["conductor"], "psid": parent_span,
                "parent_span": None, "env": {},
                "tid": parent_tid}) + "\n")
        buf = io.StringIO()
        rc = obs_main(["timeline", "--fail-on-orphans", parent_trace,
                       t1, t2], out=buf)
        out = buf.getvalue()
        assert rc == 0, out
        assert "orphans=0" in out
        assert "processes=3" in out


# ------------------------------------------------ fleet serving chaos

class _Fleet:
    """Subprocess daemons sharing one spool, found through their
    heartbeat records (`serve.json` is last-writer-wins, so a daemon's
    own port lives in `spool/daemons/<id>.json` alone)."""

    def __init__(self, spool, env, trace_dir=None):
        self.spool, self.env, self.trace_dir = spool, dict(env), trace_dir
        self.procs = []

    def start(self, n=1):
        for _ in range(n):
            args = [sys.executable, "-m", "jaxmc.serve", "run",
                    "--spool", self.spool, "--workers", "1", "--quiet"]
            if self.trace_dir:
                args += ["--trace", os.path.join(
                    self.trace_dir,
                    f"daemon{len(self.procs)}.trace.jsonl")]
            self.procs.append(subprocess.Popen(
                args, cwd=REPO, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL,
                env=dict(os.environ, JAX_PLATFORMS="cpu", **self.env)))

    def daemons(self):
        """Heartbeat records of OUR live daemons (matched by pid)."""
        import glob
        pids = {p.pid for p in self.procs if p.poll() is None}
        out = []
        for path in sorted(glob.glob(
                os.path.join(self.spool, "daemons", "*.json"))):
            try:
                with open(path) as fh:
                    rec = json.load(fh)
            except (OSError, ValueError):
                continue
            if rec.get("pid") in pids:
                out.append(rec)
        return out

    def wait_up(self, n, timeout=120.0):
        import time
        deadline = time.time() + timeout
        while time.time() < deadline:
            recs = self.daemons()
            if len(recs) >= n:
                return recs
            time.sleep(0.1)
        raise AssertionError(
            f"only {len(self.daemons())}/{n} daemons heartbeating")

    @staticmethod
    def client(rec):
        from jaxmc.serve.protocol import ServeClient
        return ServeClient(rec.get("host", "127.0.0.1"), rec["port"])

    def record(self, jid):
        """A job record straight off the spool: it must be readable with
        every daemon dead."""
        for sub in ("jobs", "quarantine"):
            try:
                with open(os.path.join(self.spool, sub,
                                       f"{jid}.json")) as fh:
                    return json.load(fh)
            except (OSError, ValueError):
                continue
        return None

    def wait_job(self, jid, statuses, timeout=180.0):
        import time
        deadline = time.time() + timeout
        last = None
        while time.time() < deadline:
            rec = self.record(jid)
            if rec is not None:
                last = rec.get("status")
                if last in statuses:
                    return rec
            time.sleep(0.1)
        raise AssertionError(f"job {jid} still {last!r}, wanted {statuses}")

    def metric_total(self, name):
        import urllib.request
        total = 0.0
        for rec in self.daemons():
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{rec['port']}/metrics",
                        timeout=10) as resp:
                    text = resp.read().decode()
            except OSError:
                continue
            for ln in text.splitlines():
                if ln.startswith(name + " "):
                    total += float(ln.rsplit(" ", 1)[1])
        return total

    def stop(self, graceful=True, timeout=30.0):
        import time
        for p in self.procs:
            if p.poll() is None and graceful:
                p.terminate()   # SIGTERM: cooperative drain, exit 0
        deadline = time.time() + timeout
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.wait(max(0.1, deadline - time.time()))
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait(10)


class TestFleetLegs:
    """The fleet substrate end to end (ISSUE 19), as the deleted `make
    fleet-check` drove it (ISSUE 43): several daemon PROCESSES on one
    durable spool, interp jobs, real SIGKILLs.  Seconds each.  (The
    admission leg needs no second process:
    tests/test_serve.py::TestAdmission.)"""

    def test_sigkill_takeover_resumes_with_the_solo_counts(self, tmp_path):
        import signal
        import time
        from conftest import write_slow_spec
        spec = write_slow_spec(tmp_path / "specs", "takeoverload",
                               q=1500, bound=20)
        opts = {"backend": "interp", "progress_every": 2}
        solo = _Fleet(str(tmp_path / "spool_solo"),
                      {"JAXMC_SERVE_CKPT_EVERY": "0.3"})
        solo.start(1)
        try:
            code, job = solo.client(solo.wait_up(1)[0]).submit(
                spec, None, opts)
            assert code == 200, job
            ref = solo.wait_job(job["id"], ("done",))
        finally:
            solo.stop()
        assert ref["ok"] is True and ref["distinct"] > 200

        fleet = _Fleet(str(tmp_path / "spool_fleet"), {
            "JAXMC_SERVE_CKPT_EVERY": "0.3", "JAXMC_LEASE_TTL": "1.5",
            "JAXMC_LEASE_AFFINITY_GRACE": "0.2"})
        fleet.start(3)
        try:
            recs = fleet.wait_up(3)
            code, job = fleet.client(recs[0]).submit(spec, None, opts)
            assert code == 200, job
            jid = job["id"]
            owner = fleet.wait_job(jid, ("running",), 120)["daemon"]
            time.sleep(1.0)     # let a spool checkpoint land
            # heartbeat ids are `d<pid>-<hex>`: the SIGKILL needs no
            # side channel
            os.kill(int(owner[1:].split("-", 1)[0]), signal.SIGKILL)
            done = fleet.wait_job(jid, ("done", "failed", "quarantined"))
            assert done["status"] == "done", done
            # a peer went through the lease steal, resumed from the spool
            # checkpoint and answered what the undisturbed run answered
            assert done["daemon"] != owner
            assert done["stolen_by"] == done["daemon"]
            assert "stolen" in done.get("requeue_note", "")
            assert (done["generated"], done["distinct"], done["ok"]) == \
                (ref["generated"], ref["distinct"], ref["ok"])
            assert fleet.metric_total("jaxmc_serve_takeovers") >= 1
        finally:
            fleet.stop()

    def test_warm_hit_routing_beats_round_robin_and_traces_stitch(
            self, tmp_path):
        import glob
        import time
        from conftest import timeline_counts, write_slow_spec
        spec = write_slow_spec(tmp_path / "specs", "routeload",
                               q=200, bound=12)
        opts = {"backend": "interp"}
        trace_dir = str(tmp_path / "traces")
        os.makedirs(trace_dir)
        fleet = _Fleet(str(tmp_path / "spool"), {
            # nothing rides the fast lane: cold signatures DEFER to the
            # fleet scan and warm affinity decides who runs them
            "JAXMC_SERVE_FASTLANE_BOUND": "0",
            "JAXMC_LEASE_AFFINITY_GRACE": "5.0"}, trace_dir=trace_dir)
        fleet.start(1)      # A alone first: a fleet of one runs locally
        try:
            rec_a = fleet.wait_up(1)[0]
            code, job = fleet.client(rec_a).submit(spec, None, opts)
            assert code == 200, job
            fleet.wait_job(job["id"], ("done",))
            fleet.start(2)  # two cold peers join
            recs = fleet.wait_up(3)
            time.sleep(1.5)  # every fleet scan sees three daemons
            jids = []
            for i in range(4):  # identical jobs, round-robin over ports
                code, job = fleet.client(recs[i % 3]).submit(
                    spec, None, opts)
                assert code == 200, job
                jids.append(job["id"])
            owners = [fleet.wait_job(j, ("done",))["daemon"] for j in jids]
            share = owners.count(rec_a["id"]) / len(owners)
            assert share > 1 / 3, (rec_a["id"], owners)
            # ... by routing, not by luck
            assert fleet.metric_total("jaxmc_serve_jobs_deferred") >= 1
            assert fleet.metric_total(
                "jaxmc_serve_affinity_adoptions") >= 1
            fleet.stop(graceful=True)
            traces = sorted(glob.glob(os.path.join(
                trace_dir, "*.trace.jsonl"))) + sorted(glob.glob(
                    os.path.join(fleet.spool, "results",
                                 "*.trace.jsonl")))
            rc, counts, out = timeline_counts(traces)
            assert rc == 0 and counts["orphans"] == 0, out[-800:]
            assert counts["processes"] >= 3, counts
        finally:
            fleet.stop()

    def test_poison_job_is_quarantined_after_the_retry_budget(
            self, tmp_path):
        import time
        from conftest import write_slow_spec
        spec = write_slow_spec(tmp_path / "specs", "poisonload",
                               q=50, bound=6)
        retries = 2
        state = str(tmp_path / "fault_state")
        os.makedirs(state)
        fleet = _Fleet(str(tmp_path / "spool"), {
            # every daemon that marks this spec running SIGKILLs itself;
            # the latch directory is SHARED, so respawned lives spend one
            # cross-daemon budget
            "JAXMC_FAULTS": "daemon_kill:spec=poisonload.tla:n=99",
            "JAXMC_FAULTS_STATE": state,
            "JAXMC_JOB_RETRIES": str(retries), "JAXMC_LEASE_TTL": "1.0",
            "JAXMC_LEASE_AFFINITY_GRACE": "0.1",
            "JAXMC_SERVE_CKPT_EVERY": "0.3"})
        fleet.start(2)
        try:
            code, job = fleet.client(fleet.wait_up(2)[0]).submit(
                spec, None, {"backend": "interp"})
            assert code == 200, job
            jid = job["id"]
            qpath = os.path.join(fleet.spool, "quarantine", f"{jid}.json")
            deadline = time.time() + 180
            respawns = 0
            while time.time() < deadline and not os.path.exists(qpath):
                live = sum(1 for p in fleet.procs if p.poll() is None)
                while live < 2 and respawns < 8:    # the supervisor
                    fleet.start(1)
                    live += 1
                    respawns += 1
                time.sleep(0.2)
            rec = fleet.record(jid) or {}
            assert rec.get("status") == "quarantined", (rec, respawns)
            assert "poison" in rec["verdict"]
            assert rec["retries_spent"] == retries
            assert rec.get("fault_context")
            # a live daemon answers for the id with that verdict, no 404
            code, got = fleet.client(fleet.wait_up(1)[0]).job(jid)
            assert code == 200 and got["status"] == "quarantined"
            assert got["verdict"] == rec["verdict"]
        finally:
            fleet.stop(graceful=False)


@pytest.mark.slow
class TestFleetChaos:
    """ISSUE 19: subprocess daemons sharing one durable spool, under
    the daemon_kill / lease_stall fault sites.  Slow-marked (multi-
    second subprocess scenarios); the acceptance legs themselves are
    `TestFleetLegs` above, in tier-1."""

    def _start_daemon(self, spool, extra_env=None):
        env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
        return subprocess.Popen(
            [sys.executable, "-m", "jaxmc.serve", "run", "--spool",
             spool, "--workers", "1", "--quiet"],
            cwd=REPO, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)

    def _heartbeat(self, spool, pid, timeout=120):
        """This pid's heartbeat record (carries its id + bound port)."""
        import glob
        import time
        deadline = time.time() + timeout
        while time.time() < deadline:
            for path in glob.glob(os.path.join(spool, "daemons",
                                               "*.json")):
                try:
                    with open(path) as fh:
                        rec = json.load(fh)
                except (OSError, ValueError):
                    continue
                if rec.get("pid") == pid:
                    return rec
            time.sleep(0.1)
        raise AssertionError(f"daemon pid {pid} never heartbeated")

    def test_daemon_sigkill_mid_vbatch_cohort_reforms(self, tmp_path):
        # the daemon that popped a 4-member layout-compat cohort
        # SIGKILLs itself right after marking the members running
        # (daemon_kill kind=vbatch); the next daemon life must steal
        # the expired leases, RE-FORM the cohort, and answer every
        # member with counts identical to solo runs
        import time
        from jaxmc.serve import JobQueue
        from jaxmc.serve.protocol import build_config, job_signature
        from jaxmc.session import batch_profile

        spool = str(tmp_path / "spool")
        bt = os.path.join(SPECS, "batchtoy.tla")
        opts = {"backend": "jax", "platform": "cpu", "host_seen": True}
        q = JobQueue(spool)
        jids = []
        for v in ("a", "b", "c", "d"):
            cfg = build_config(bt, os.path.join(
                SPECS, f"batchtoy_{v}.cfg"), opts)
            prof = batch_profile(cfg)
            job = q.new_job(cfg.spec, cfg.cfg, opts,
                            job_signature(cfg),
                            bsig=prof.bsig if prof else None,
                            cost_estimate=prof.cost_estimate
                            if prof else None)
            jids.append(job["id"])

        a = self._start_daemon(spool, {
            "JAXMC_FAULTS": "daemon_kill:kind=vbatch:n=1",
            "JAXMC_LEASE_TTL": "1.0"})
        a.wait(timeout=240)
        assert a.returncode in (-9, 137), \
            f"daemon A exited {a.returncode}, expected the injected " \
            f"SIGKILL"

        b = self._start_daemon(spool, {"JAXMC_LEASE_TTL": "1.0"})
        try:
            rec_b = self._heartbeat(spool, b.pid)
            recs = {}
            deadline = time.time() + 300
            while time.time() < deadline and len(recs) < len(jids):
                assert b.poll() is None, "daemon B died"
                for j in jids:
                    rec = q.load(j)
                    if rec and rec.get("status") == "done":
                        recs[j] = rec
                time.sleep(0.2)
            assert len(recs) == len(jids), \
                f"only {sorted(recs)} of {jids} finished"
            for v, j in zip(("a", "b", "c", "d"), jids):
                solo = _cli([bt, "--cfg",
                             os.path.join(SPECS, f"batchtoy_{v}.cfg"),
                             "--quiet"])
                assert solo.returncode == 0, solo.stderr
                gen, dis = _counts(solo.stdout)
                rec = recs[j]
                assert rec["daemon"] == rec_b["id"]
                assert rec.get("stolen_by") == rec_b["id"]
                assert (rec["generated"], rec["distinct"]) == \
                    (gen, dis), f"member {v} diverged after takeover"
                # the cohort RE-FORMED (members ran batched, not solo)
                assert rec.get("batch_occupancy", 1) >= 2, \
                    f"member {v} ran solo after the steal " \
                    f"(occupancy {rec.get('batch_occupancy')})"
        finally:
            b.terminate()
            try:
                b.wait(timeout=60)
            except subprocess.TimeoutExpired:
                b.kill()

    def test_lease_stall_double_claim_single_winner(self, tmp_path):
        # daemon A claims a slow job but its fleet loop stalls
        # (lease_stall): no renewals, no heartbeats, while its worker
        # keeps running.  Peer B must steal the expired lease and win;
        # A must DROP its late result (serve.lease_lost_drops) so
        # exactly one daemon publishes
        import time
        import urllib.request
        from jaxmc.serve import JobQueue
        from jaxmc.serve.protocol import ServeClient
        from conftest import SLOW_CFG as _SLOW_CFG, SLOW_SPEC as _SLOW_SPEC

        spec = str(tmp_path / "stallload.tla")
        with open(spec, "w") as fh:
            fh.write(_SLOW_SPEC.format(q=1500, bound=20)
                     .replace("MODULE traceload", "MODULE stallload"))
        with open(str(tmp_path / "stallload.cfg"), "w") as fh:
            fh.write(_SLOW_CFG)
        solo = _cli([spec, "--quiet"])
        assert solo.returncode == 0, solo.stderr
        ref = _counts(solo.stdout)

        spool = str(tmp_path / "spool")
        a = self._start_daemon(spool, {
            "JAXMC_FAULTS": "lease_stall:n=999",
            "JAXMC_LEASE_TTL": "1.0"})
        b = None
        try:
            rec_a = self._heartbeat(spool, a.pid)
            client = ServeClient(rec_a.get("host", "127.0.0.1"),
                                 rec_a["port"])
            code, job = client.submit(spec, None,
                                      {"backend": "interp"})
            assert code == 200, f"submit failed ({code}): {job}"
            jid = job["id"]
            q = JobQueue(spool)
            deadline = time.time() + 120
            while time.time() < deadline:
                rec = q.load(jid) or {}
                if rec.get("status") == "running" and \
                        rec.get("daemon") == rec_a["id"]:
                    break
                time.sleep(0.1)
            else:
                raise AssertionError(f"A never claimed {jid}")

            b = self._start_daemon(spool, {
                "JAXMC_LEASE_TTL": "1.0",
                "JAXMC_LEASE_AFFINITY_GRACE": "0.1"})
            rec_b = self._heartbeat(spool, b.pid)
            deadline = time.time() + 240
            while time.time() < deadline:
                rec = q.load(jid) or {}
                if rec.get("status") == "done":
                    break
                time.sleep(0.2)
            assert rec.get("status") == "done", \
                f"job ended {rec.get('status')!r}"
            # exactly one winner: B, through the lease steal
            assert rec["daemon"] == rec_b["id"]
            assert rec.get("stolen_by") == rec_b["id"]
            assert "stolen" in rec.get("requeue_note", "")
            assert (rec["generated"], rec["distinct"]) == ref
            # the stalled loser must DROP its late copy at publish
            # time (the fleet tick that counts serve.lease_lost is
            # exactly what the stall suppresses, so the ownership
            # check in _publishable is the arbitration under test)
            deadline = time.time() + 120
            stalls = drops = 0.0
            while time.time() < deadline:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{rec_a['port']}/metrics",
                        timeout=10) as resp:
                    text = resp.read().decode()
                vals = {}
                for ln in text.splitlines():
                    if ln.startswith("jaxmc_serve_lease_"):
                        name, _, v = ln.rpartition(" ")
                        vals[name] = float(v)
                stalls = vals.get("jaxmc_serve_lease_stalls", 0.0)
                drops = vals.get("jaxmc_serve_lease_lost_drops", 0.0)
                if stalls >= 1 and drops >= 1:
                    break
                time.sleep(0.5)
            assert stalls >= 1, "the lease_stall fault never fired"
            assert drops >= 1, "stalled daemon published a stolen " \
                               "job's result — two winners"
        finally:
            for p in (a, b):
                if p is None:
                    continue
                p.terminate()
                try:
                    p.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    p.kill()
