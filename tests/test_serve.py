r"""jaxmc.serve: the checking-as-a-service daemon (ISSUE 7).

Covers the acceptance surface end to end:
  - submit/poll/result round-trip over a REAL socket (the daemon's own
    HTTP listener, in-process for speed);
  - durable spool: a daemon started over a non-empty on-disk queue
    answers every job; identical queued jobs BATCH through one run;
  - warm second submission: same daemon, identical job — the warm
    session resumes the first job's FINAL checkpoint with
    window_recompiles == 0 and a capacity-profile hit (the jax resident
    scenario is the acceptance criterion verbatim);
  - daemon restart: the signature-keyed checkpoint + persistent compile
    cache + capacity profile make the next life's identical job a
    resume with nonzero persistent-cache hits;
  - SIGTERM drain (real subprocess): the in-flight job checkpoints and
    parks, queued jobs survive, no orphan workers, no open spans in the
    trace, and the next daemon life re-answers everything from
    checkpoints — no job lost.
"""

import json
import os
import re
import signal
import subprocess
import sys
import time

import pytest

from jaxmc import drain
from jaxmc.engine.explore import Explorer
from jaxmc.serve import JobQueue, ServeDaemon
from jaxmc.serve.protocol import (ServeClient, build_config,
                                  job_signature)
from jaxmc.session import load_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = os.path.join(REPO, "specs")


def spec(name):
    return os.path.join(SPECS, f"{name}.tla")


_EXPECT = {}


def expect(name, max_states=None):
    """Reference counts from the serial engine (cached per suite)."""
    key = (name, max_states)
    if key not in _EXPECT:
        _EXPECT[key] = Explorer(load_model(spec(name), None, False),
                                max_states=max_states).run()
    return _EXPECT[key]


@pytest.fixture(autouse=True)
def _clean_drain():
    drain.clear()
    yield
    drain.clear()


@pytest.fixture()
def spool(tmp_path):
    return str(tmp_path / "spool")


@pytest.fixture()
def daemon(spool):
    d = ServeDaemon(spool, workers=1, quiet=True).start()
    yield d
    d.shutdown()


def client(d):
    return ServeClient("127.0.0.1", d.port)


JAX_OPTS = {"backend": "jax", "platform": "cpu", "resident": True,
            "no_trace": True}


def start_subprocess_daemon(spool, trace=None, extra_env=None):
    """A REAL daemon process (the restart/SIGTERM scenarios need
    process death, not object teardown).  Returns (Popen, client)."""
    args = [sys.executable, "-m", "jaxmc.serve", "run",
            "--spool", spool, "--workers", "1", "--quiet"]
    if trace:
        args += ["--trace", trace]
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    p = subprocess.Popen(args, cwd=REPO, stdout=subprocess.DEVNULL,
                         stderr=subprocess.PIPE, text=True, env=env)
    stamp = os.path.join(spool, "serve.json")
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            with open(stamp) as fh:
                info = json.load(fh)
            if info.get("status") == "serving" and \
                    info.get("pid") == p.pid:
                return p, ServeClient(info["host"], info["port"])
        except (OSError, ValueError):
            pass
        assert p.poll() is None, p.stderr.read()
        time.sleep(0.1)
    raise AssertionError("daemon did not stamp the spool in time")


class TestRoundTrip:
    def test_submit_poll_result_over_socket(self, daemon):
        c = client(daemon)
        code, job = c.submit(spec("viewtoy"))
        assert code == 200 and job["status"] == "queued" and job["sig"]
        done = c.wait(job["id"], timeout=60)
        assert done["status"] == "done" and done["ok"]
        code, res = c.result(job["id"])
        assert code == 200
        exp = expect("viewtoy")
        assert res["result"]["distinct"] == exp.distinct
        assert res["result"]["generated"] == exp.generated
        assert str(res["schema"]).startswith("jaxmc.metrics")
        assert res["serve"]["sig"] == job["sig"]
        code, st = c.status()
        assert code == 200 and st["queue_depth"] == 0
        assert st["counters"].get("serve.jobs_done") == 1

    def test_violation_job_carries_trace(self, daemon):
        c = client(daemon)
        _, job = c.submit(spec("symtoy"))
        done = c.wait(job["id"], timeout=60)
        assert done["status"] == "done" and done["ok"] is False
        _, res = c.result(job["id"])
        assert res["result"]["ok"] is False
        assert res["result"]["violation"]["kind"] == "deadlock"
        assert "Error: Deadlock reached." in res["result"]["trace"]
        assert "The behavior up to this point is:" in \
            res["result"]["trace"]

    def test_bad_jobs_rejected(self, daemon):
        c = client(daemon)
        code, body = c.submit(spec("nonexistent_spec"))
        assert code == 400 and "not found" in body["error"]
        code, body = c.submit(spec("viewtoy"),
                              options={"checkpoint": "/tmp/x"})
        assert code == 400 and "forbidden" in body["error"]
        code, body = c.job("j99999999")
        assert code == 404


class TestDurableQueue:
    def test_restart_answers_nonempty_on_disk_queue(self, spool):
        # jobs land in the spool with NO daemon alive; the next daemon
        # start finds and answers them — the restart-survival contract
        q = JobQueue(spool)
        ids = []
        for name in ("viewtoy", "constoy"):
            cfg = build_config(spec(name), None, {})
            ids.append(q.new_job(spec(name), None, {},
                                 job_signature(cfg))["id"])
        d = ServeDaemon(spool, workers=1, quiet=True).start()
        try:
            c = client(d)
            for jid, name in zip(ids, ("viewtoy", "constoy")):
                rec = c.wait(jid, timeout=60)
                assert rec["status"] == "done", rec
                assert rec["distinct"] == expect(name).distinct
        finally:
            d.shutdown()

    def test_identical_queued_jobs_batch_through_one_run(self, spool):
        q = JobQueue(spool)
        cfg = build_config(spec("constoy"), None, {})
        sig = job_signature(cfg)
        ids = [q.new_job(spec("constoy"), None, {}, sig)["id"]
               for _ in range(3)]
        d = ServeDaemon(spool, workers=1, quiet=True).start()
        try:
            c = client(d)
            recs = [c.wait(jid, timeout=60) for jid in ids]
            assert all(r["status"] == "done" for r in recs)
            followers = [r for r in recs if r.get("batch_leader")]
            assert len(followers) == 2, \
                "identical queued jobs must coalesce into one dispatch"
            assert d.tel.counters.get("serve.batched_jobs") == 2
            exp = expect("constoy")
            for jid in ids:
                res = q.load_result(jid)
                assert res["result"]["distinct"] == exp.distinct
        finally:
            d.shutdown()


class TestWarmReuse:
    def test_warm_second_submission_interp(self, daemon):
        c = client(daemon)
        _, j1 = c.submit(spec("constoy"))
        r1 = c.wait(j1["id"], timeout=60)
        _, j2 = c.submit(spec("constoy"))
        r2 = c.wait(j2["id"], timeout=60)
        assert j1["sig"] == j2["sig"]
        assert r1["warm_engine"] is False
        assert r2["warm_engine"] is True
        assert r2["resumed_from_checkpoint"] is True
        assert (r2["distinct"], r2["generated"]) == \
            (r1["distinct"], r1["generated"])
        assert daemon.tel.counters.get("serve.warm_hits") == 1

    def test_warm_jax_resident_zero_recompiles(self, daemon,
                                               monkeypatch, tmp_path):
        # the acceptance criterion verbatim: a second identical spec+cfg
        # job to a warm daemon resumes the first job's checkpoint with
        # window_recompiles == 0 and nonzero capacity-profile hits
        monkeypatch.setenv("JAXMC_PROFILE_STORE",
                           str(tmp_path / "profiles"))
        c = client(daemon)
        _, j1 = c.submit(spec("constoy"), options=JAX_OPTS)
        r1 = c.wait(j1["id"], timeout=180)
        assert r1["status"] == "done", r1
        _, j2 = c.submit(spec("constoy"), options=JAX_OPTS)
        r2 = c.wait(j2["id"], timeout=120)
        assert r2["status"] == "done", r2
        _, res2 = c.result(j2["id"])
        sv = res2["serve"]
        assert sv["warm_engine"] is True
        assert sv["resumed_from_checkpoint"] is True
        assert sv["window_recompiles"] == 0
        assert sv["profile_hits"] >= 1
        assert (r2["distinct"], r2["generated"]) == \
            (r1["distinct"], r1["generated"])
        exp = expect("constoy")
        assert r2["distinct"] == exp.distinct
        # the warm artifact is a normal metrics summary: the session's
        # search span lands in THIS job's recorder, not the cold job's
        assert "search" in {p["name"] for p in res2["phases"]}

    def test_warm_resubmission_under_a_seen_cap(self, daemon, monkeypatch,
                                                tmp_path):
        # ISSUE 32: cold tiers are state of ONE search.  A capped job
        # spills; the identical job on the warm session resumes the
        # first's final checkpoint (which carries the cold runs) on an
        # engine that has searched before, and must answer the same
        monkeypatch.setenv("JAXMC_PROFILE_STORE",
                           str(tmp_path / "profiles"))
        monkeypatch.setenv("JAXMC_SEEN_CAP", "512")
        c = client(daemon)
        answers = []
        for _ in range(3):
            _, j = c.submit(spec("ooc_scaled"), options=JAX_OPTS)
            r = c.wait(j["id"], timeout=240)
            assert r["status"] == "done", r
            _, res = c.result(j["id"])
            # the fresh search spilled; a resume holds what the
            # checkpoint carried, whatever the engine searched before
            assert res["gauges"]["tier.occupancy"]["host"] == 2784
            answers.append((r["generated"], r["distinct"],
                            res["serve"]["warm_engine"]))
        assert answers == [(12289, 3072, False), (12289, 3072, True),
                           (12289, 3072, True)]

    def test_warm_second_submission_jax_level_mode(self, daemon):
        # the DEFAULT device mode (level, traces on) also finalizes a
        # checkpoint on completion: a repeat submission must warm-resume
        # it, not silently re-search
        opts = {"backend": "jax", "platform": "cpu"}
        c = client(daemon)
        _, j1 = c.submit(spec("constoy"), options=opts)
        r1 = c.wait(j1["id"], timeout=180)
        assert r1["status"] == "done", r1
        _, j2 = c.submit(spec("constoy"), options=opts)
        r2 = c.wait(j2["id"], timeout=120)
        assert r2["status"] == "done", r2
        assert r2["warm_engine"] is True
        assert r2["resumed_from_checkpoint"] is True
        assert (r2["distinct"], r2["generated"]) == \
            (r1["distinct"], r1["generated"])

    def test_warm_registry_lru_eviction(self, spool, monkeypatch):
        # ISSUE 10 satellite (ROADMAP item 3): JAXMC_SERVE_WARM_MAX
        # bounds the warm CheckSession registry.  With a 1-session cap,
        # a second signature evicts the first (serve.evictions); the
        # re-submission after eviction is answered from the
        # FINAL-CHECKPOINT resume path — bit-identical, just cold
        monkeypatch.setenv("JAXMC_SERVE_WARM_MAX", "1")
        d = ServeDaemon(spool, workers=1, quiet=True).start()
        try:
            c = client(d)
            _, j1 = c.submit(spec("constoy"))
            r1 = c.wait(j1["id"], timeout=60)
            assert r1["status"] == "done"
            sig1 = j1["sig"]
            _, j2 = c.submit(spec("viewtoy"))
            r2 = c.wait(j2["id"], timeout=60)
            assert r2["status"] == "done"
            assert d.warm_max == 1
            assert d.tel.counters.get("serve.evictions") == 1
            assert sig1 not in d.warm and j2["sig"] in d.warm
            # resubmit the evicted signature: cold engine, but the
            # spool checkpoint survives eviction — same answer
            _, j3 = c.submit(spec("constoy"))
            r3 = c.wait(j3["id"], timeout=60)
            assert r3["status"] == "done"
            assert r3["warm_engine"] is False
            assert r3["resumed_from_checkpoint"] is True
            assert (r3["distinct"], r3["generated"]) == \
                (r1["distinct"], r1["generated"])
            assert d.tel.counters.get("serve.ckpt_resumes") == 1
        finally:
            d.shutdown()

    def test_restart_resumes_with_persistent_cache_hits(
            self, spool, tmp_path):
        # across daemon LIVES (real processes — an in-process pair
        # would be short-circuited by jax's in-memory caches) the
        # durable artifacts carry the warmth: the signature-keyed final
        # checkpoint (resume), the capacity profile (caps), and the
        # persistent compile cache (the one fresh XLA program becomes a
        # disk hit)
        # the cache is placed from OUTSIDE, the way every process is
        # told where it lives; the capacity profiles travel inside it
        extra_env = {
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla_cache"),
            "JAXMC_COMPILE_CACHE": "on",  # conftest opts the suite out
            "JAXMC_CACHE_PROBE": "0",
        }
        q = JobQueue(spool)
        p1, c1 = start_subprocess_daemon(spool, extra_env=extra_env)
        try:
            _, j1 = c1.submit(spec("constoy"), options=JAX_OPTS)
            r1 = c1.wait(j1["id"], timeout=180)
            assert r1["status"] == "done", r1
            c1.drain()
            assert p1.wait(timeout=60) == 0
        finally:
            if p1.poll() is None:
                p1.kill()
        p2, c2 = start_subprocess_daemon(spool, extra_env=extra_env)
        try:
            _, j2 = c2.submit(spec("constoy"), options=JAX_OPTS)
            r2 = c2.wait(j2["id"], timeout=180)
            assert r2["status"] == "done", r2
            res2 = q.load_result(j2["id"])
            sv = res2["serve"]
            assert sv["warm_engine"] is False  # new process, new engine
            assert sv["resumed_from_checkpoint"] is True
            assert sv["profile_hits"] >= 1
            assert sv["persistent_cache_hits"] >= 1
            assert os.listdir(tmp_path / "xla_cache" / "profiles")
            assert (r2["distinct"], r2["generated"]) == \
                (r1["distinct"], r1["generated"])
            c2.drain()
            assert p2.wait(timeout=60) == 0
        finally:
            if p2.poll() is None:
                p2.kill()


class TestSigtermDrain:
    def test_sigterm_drains_inflight_and_restart_loses_nothing(
            self, spool, tmp_path):
        trace = str(tmp_path / "fleet.jsonl")
        limit = 30000
        p, c = start_subprocess_daemon(spool, trace=trace)
        try:
            _, slow = c.submit(spec("transfer_scaled"),
                               options={"max_states": limit})
            _, queued = c.submit(spec("viewtoy"))
            # wait until the slow job is actually IN FLIGHT
            deadline = time.time() + 30
            while time.time() < deadline:
                _, st = c.status()
                if slow["id"] in st.get("running", {}):
                    break
                time.sleep(0.1)
            else:
                raise AssertionError("slow job never started")
            time.sleep(1.0)  # well inside the multi-second search
            p.send_signal(signal.SIGTERM)
            rc = p.wait(timeout=120)
        finally:
            if p.poll() is None:
                p.kill()
        assert rc == 0, p.stderr.read()

        q = JobQueue(spool)
        slow_rec = q.load(slow["id"])
        assert slow_rec["status"] == "drained", slow_rec
        assert os.path.exists(q.ckpt_path(slow["sig"])), \
            "drained job must leave a checkpoint"
        assert q.load(queued["id"])["status"] == "queued", \
            "queued job must survive the drain untouched"
        # no open spans in the fleet trace = nothing leaked at drain
        events = [json.loads(ln) for ln in open(trace)]
        opens = sum(1 for e in events if e["ev"] == "span_open")
        closes = sum(1 for e in events if e["ev"] == "span")
        assert opens == closes, "drain left open spans"
        assert any(e["ev"] == "run_end" for e in events)

        # ---- next daemon life: both jobs answered, from checkpoints --
        p2, c2 = start_subprocess_daemon(spool)
        try:
            done_slow = c2.wait(slow["id"], timeout=120)
            assert done_slow["status"] == "done", done_slow
            assert done_slow["resumed_from_checkpoint"] is True
            exp = expect("transfer_scaled", max_states=limit)
            assert (done_slow["distinct"], done_slow["generated"]) == \
                (exp.distinct, exp.generated), \
                "drain+resume must be bit-identical to an uninterrupted run"
            done_q = c2.wait(queued["id"], timeout=60)
            assert done_q["status"] == "done"
            assert done_q["distinct"] == expect("viewtoy").distinct
            c2.drain()
            rc2 = p2.wait(timeout=60)
            assert rc2 == 0
        finally:
            if p2.poll() is None:
                p2.kill()


class TestMetricsRetention:
    """ISSUE 17 satellites: per-job /metrics series outlive the job for
    JAXMC_METRICS_JOB_TTL seconds (a coarse scraper still sees a short
    job's final series), and jax jobs expose jaxmc_prof_site_* gauges
    from the always-on profiler plus the measured device peak."""

    def test_done_job_series_ttl_and_prof_gauges(self, daemon,
                                                 monkeypatch,
                                                 tmp_path):
        monkeypatch.setenv("JAXMC_PROFILE_STORE",
                           str(tmp_path / "profiles"))
        c = client(daemon)
        _, job = c.submit(spec("constoy"), options=JAX_OPTS)
        done = c.wait(job["id"], timeout=180)
        assert done["status"] == "done", done
        jid = job["id"]
        # completed job: the final series linger inside the TTL window
        body = daemon.metrics_text()
        assert f'jaxmc_job_running{{job="{jid}"}} 0' in body
        assert f'jaxmc_prof_site_dispatches{{job="{jid}",' \
               f'site="bfs.resident_run"}}' in body
        # the device peak is the MEASURED one or the series is omitted:
        # XLA:CPU reports no memory_stats, so this job serves none...
        assert "jaxmc_hbm_peak_bytes" not in body
        # ...and a job whose artifact carries a measured peak serves it
        from jaxmc.serve.daemon import _ArtifactSeries
        with daemon._cv:
            daemon._done_series["chipjob"] = (time.time(), _ArtifactSeries(
                {"prof": {"sites": {}, "hbm": {"peak_bytes": 208273408}}}))
        assert 'jaxmc_hbm_peak_bytes{job="chipjob"} 208273408' in \
            daemon.metrics_text()
        # advance the metrics clock past the TTL: the series are pruned
        t0 = time.time()
        daemon._metrics_clock = \
            lambda: t0 + daemon._job_ttl + 1.0
        body2 = daemon.metrics_text()
        assert jid not in body2
        # fleet-level series survive the prune
        assert "jaxmc_serve_jobs_done" in body2


class TestDeviceOwnerDefault:
    """ISSUE 19 satellite: device work leaves the daemon process BY
    DEFAULT now that owner death is supervised (requeue + respawn +
    the cross-daemon retry budget); JAXMC_SERVE_DEVICE_OWNER=0 (or
    `run --no-device-owner`) opts back into the pre-fleet in-process
    layout.  The owner spawn itself is lazy, so constructing the
    daemon does not fork."""

    def test_owner_enabled_by_default(self, tmp_path, monkeypatch):
        monkeypatch.delenv("JAXMC_SERVE_DEVICE_OWNER", raising=False)
        d = ServeDaemon(str(tmp_path / "spool"), workers=1, quiet=True)
        assert d.owner is not None
        assert d.owner.pid is None  # lazy: nothing forked yet
        d.owner.stop()

    def test_env_zero_opts_out(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JAXMC_SERVE_DEVICE_OWNER", "0")
        d = ServeDaemon(str(tmp_path / "spool"), workers=1, quiet=True)
        assert d.owner is None


# ---- harvested from the deleted `make fleet-check` / `make trace-check`
# ---- harnesses (ISSUE 43): what they asserted, as tier-1 tests

class TestAdmission:
    """A depth-bounded daemon under a submit burst: the overflow gets a
    FAST 429 with `Retry-After` and the queue gauges in the body, the
    admission counter moves, and every ACCEPTED job still completes."""

    def test_burst_over_depth_bound_answers_429(self, spool, tmp_path,
                                                monkeypatch):
        from conftest import write_slow_spec
        monkeypatch.setenv("JAXMC_SERVE_MAX_DEPTH", "2")
        slow = write_slow_spec(tmp_path / "specs", "admitload",
                               q=600, bound=16)
        d = ServeDaemon(spool, workers=1, quiet=True).start()
        try:
            assert d.max_depth == 2
            c = client(d)
            accepted, rejected = [], []
            for _ in range(8):
                code, job = c.submit(slow, None, {"backend": "interp"},
                                     tenant="burst")
                assert code in (200, 429), (code, job)
                if code == 200:
                    accepted.append(job["id"])
                else:
                    rejected.append((dict(c.last_headers), job))
            # one running + one pending fill the bound; the rest bounce
            assert len(accepted) == 2 and len(rejected) == 6
            for headers, body in rejected:
                assert float(headers["Retry-After"]) >= 1
                assert body["reason"] == "queue_full"
                assert body["queue_depth"] == 2 == body["max_depth"]
                assert body["tenant"] == "burst"
                assert body["retry_after_s"] >= 1.0
                assert "admission refused" in body["error"]
            assert d.tel.counters["serve.admission_rejected"] == 6
            assert "jaxmc_serve_admission_rejected 6" in d.metrics_text()
            for jid in accepted:
                done = c.wait(jid, timeout=180)
                assert done["status"] == "done", done
                assert done["ok"] is True
            # the bound is on depth, not a latch: room again, 200 again
            code, job = c.submit(slow, None, {"backend": "interp"},
                                 tenant="burst")
            assert code == 200, job
            assert c.wait(job["id"], timeout=180)["status"] == "done"
        finally:
            d.shutdown()


#: one Prometheus text 0.0.4 sample line: name{labels}? value
_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})?"
    r" -?\d+(\.\d+)?([eE][-+]?\d+)?$")


def _prom_value(text, name, jid=None):
    want = name + ('{job="%s"} ' % jid if jid else " ")
    for ln in text.splitlines():
        if ln.startswith(want):
            return float(ln.rsplit(" ", 1)[1])
    return None


class TestLiveObservability:
    """One daemon's observability surface, scraped while a job runs: a
    slow interp job with a fork pool, its identical resubmission, and a
    jax job in the device-owner process."""

    def test_metrics_parse_progress_moves_and_timeline_stitches(
            self, spool, tmp_path, monkeypatch):
        import glob
        import urllib.request
        from conftest import timeline_counts, write_slow_spec
        monkeypatch.setenv("JAXMC_SERVE_DEVICE_OWNER", "1")
        monkeypatch.setenv("JAXMC_PROFILE_STORE",
                           str(tmp_path / "profiles"))
        monkeypatch.setenv("JAXMC_HEARTBEAT_EVERY", "2")
        slow = write_slow_spec(tmp_path / "specs", "traceload",
                               q=1500, bound=20)
        opts = {"backend": "interp", "workers": 2, "progress_every": 2}
        daemon_trace = str(tmp_path / "daemon.trace.jsonl")
        d = ServeDaemon(spool, workers=2, trace=daemon_trace,
                        quiet=True).start()

        def scrape():
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{d.port}/metrics",
                    timeout=10) as resp:
                assert "text/plain" in resp.headers.get("Content-Type")
                return resp.read().decode()

        try:
            c = client(d)
            code, job = c.submit(slow, None, opts)
            assert code == 200, job
            jid = job["id"]
            est, bad_lines, events_midrun = [], [], False
            deadline = time.time() + 240
            while True:
                _, rec = c.job(jid)
                st = rec.get("status")
                text = scrape()
                bad_lines += [ln for ln in text.splitlines()
                              if ln and not ln.startswith("#")
                              and not _PROM_SAMPLE.match(ln)]
                v = _prom_value(text, "jaxmc_search_progress_est", jid)
                if v is not None and st == "running":
                    est.append(v)
                if not events_midrun and st == "running":
                    ecode, ebody = c._request("GET",
                                              f"/jobs/{jid}/events")
                    events_midrun = ecode == 200 and \
                        bool(ebody.get("events"))
                if st in ("done", "failed", "drained"):
                    break
                assert time.time() < deadline, f"slow job still {st!r}"
                time.sleep(0.3)
            assert st == "done", rec
            # every sample line of every scrape is Prometheus text
            assert not bad_lines, bad_lines[:3]
            # the per-job progress estimate is there and MOVES mid-run
            assert len(set(est)) >= 2 and est[-1] > est[0], est[:8]
            assert all(0.0 <= v <= 1.0 for v in est), est
            # the bounded event ring answers while the job runs
            assert events_midrun

            # the identical resubmission is a warm hit
            code, wjob = c.submit(slow, None, opts)
            assert code == 200, wjob
            assert c.wait(wjob["id"], timeout=240)["status"] == "done"
            text = scrape()
            assert _prom_value(text, "jaxmc_serve_warm_hits") >= 1
            assert _prom_value(text, "jaxmc_serve_jobs_submitted") >= 2
            assert _prom_value(text, "jaxmc_serve_queue_depth") is not None

            # a jax job goes to the device-owner process: a third kind
            # of OS process in the trace
            code, ojob = c.submit(spec("constoy"), None, JAX_OPTS)
            assert code == 200, ojob
            orec = c.wait(ojob["id"], timeout=240)
            assert orec["status"] == "done", orec
            assert (orec["generated"], orec["distinct"]) == (43, 21)

            # daemon + fork workers + owner: one timeline, no orphans
            traces = [daemon_trace] + sorted(glob.glob(
                os.path.join(spool, "results", "*.trace.jsonl")))
            rc, counts, out = timeline_counts(traces)
            assert rc == 0 and counts["orphans"] == 0, out[-800:]
            assert counts["processes"] >= 3, counts
        finally:
            d.shutdown()
