r"""The serve daemon: a bounded worker pool over the durable spool,
warm CheckSessions, and the fleet telemetry dashboard.

Life of a job (see serve/__init__.py for the system view):

  submit   POST /jobs validates the payload (serve/protocol.py), stamps
           the job SIGNATURE, persists the record (serve/queue.py) and
           wakes a worker — 503 once a drain began;
  batch    the worker that pops a job also claims every QUEUED job with
           the same signature: one engine run answers all of them (for
           the resident engine that is literally one batched kernel
           dispatch sequence), counter `serve.batched_jobs`;
  warm     a signature seen before reuses its WARM CheckSession — the
           already-compiled engine — and resumes the signature-keyed
           checkpoint the previous run finalized: the repeat submission
           replays the stored verdict with zero in-window recompiles
           (`serve.warm_hits`); a cold daemon with a spool checkpoint
           from a previous life still resumes it (`serve.ckpt_resumes`)
           and re-pays only the compile, which the persistent compile
           cache + capacity profile make a disk hit;
  drain    SIGTERM / POST /drain: no new jobs, in-flight engines
           checkpoint at their next safe boundary (jaxmc/drain.py),
           their jobs park as `drained` (re-queued by the next daemon
           life's recover()), workers join, spans close, the watchdog
           stops, the fleet metrics artifact is written.

Telemetry: the daemon owns one fleet Telemetry (per-job `job` spans,
queue-depth/warm-hit/batched-jobs gauges, watchdog heartbeats); each
job ALSO records into a private per-thread recorder (obs.use_local) so
its own spans/levels/counters land in `<spool>/results/<id>.json` as a
normal jaxmc.metrics/3 artifact — `python -m jaxmc.obs report/diff`
works on serve results unchanged.  Each job's recorder additionally
writes a per-job trace (`<spool>/results/<id>.trace.jsonl`, trace
context inherited from the daemon so `obs timeline` stitches daemon +
owner + job into one tree), keeps a bounded in-memory event ring
served live at `GET /jobs/<id>/events`, and runs under its OWN
watchdog (a slow tenant cannot mask another job's stall).  `GET
/metrics` renders the whole fleet as Prometheus text without ever
touching a job thread.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from .. import drain, obs
from ..session import CheckSession
from .protocol import STATIONS, BadJob, build_config, job_signature
from .queue import JobQueue


class _ArtifactSeries:
    """Adapts a finished job's metrics ARTIFACT to the /metrics
    done-series surface (metrics_snapshot / prof / progress_est).  The
    device owner is the default device path since ISSUE 19, so the
    job's live recorder finishes in the OWNER process — the daemon
    renders the TTL-retained final series (running 0, prof sites, hbm
    peak) from the summary the owner shipped back instead."""

    progress_est = None

    class _Site:
        __slots__ = ("dispatches", "wall_s")

    class _Prof:
        __slots__ = ("sites", "hbm_peak_bytes")

    def __init__(self, summary: Dict[str, Any]):
        self._counters = dict(summary.get("counters") or {})
        self._gauges = dict(summary.get("gauges") or {})
        self._levels = list(summary.get("levels") or [])
        self.t_start = summary.get("started_at") or time.time()
        self.prof = None
        pb = summary.get("prof")
        if isinstance(pb, dict):
            prof = self._Prof()
            prof.sites = {}
            prof.hbm_peak_bytes = \
                (pb.get("hbm") or {}).get("peak_bytes")
            for name, sd in sorted((pb.get("sites") or {}).items()):
                st = self._Site()
                st.dispatches = sd.get("dispatches", 0)
                st.wall_s = sd.get("wall_s", 0.0)
                prof.sites[name] = st
            self.prof = prof

    def metrics_snapshot(self) -> Dict[str, Any]:
        return {"counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "levels": list(self._levels)}

    def recent_events(self) -> List[Dict[str, Any]]:
        return []


class ServeDaemon:
    def __init__(self, spool: str, host: str = "127.0.0.1",
                 port: int = 0, workers: int = 2,
                 trace: Optional[str] = None,
                 metrics_out: Optional[str] = None,
                 quiet: bool = False,
                 checkpoint_every: float = 60.0):
        # a fresh daemon re-arms the drain flag: an in-process restart
        # (tests, the smoke gate) must not inherit the last life's drain
        drain.clear()
        self.q = JobQueue(spool)
        self.tel = obs.Telemetry(
            trace_path=trace,
            meta={"command": "serve", "spool": self.q.root,
                  "env": obs.environment_meta()})
        # spool writes surface their retry/degrade telemetry here
        self.q.tel = self.tel
        self.log = obs.Logger(self.tel, quiet=quiet)
        # FLEET IDENTITY (ISSUE 19): several daemons may share one
        # spool; each carries a unique id stamped into its heartbeats,
        # leases, and job records so takeovers are attributable
        self.daemon_id = f"d{os.getpid()}-{os.urandom(3).hex()}"

        def _fenv(name: str, default: float) -> float:
            try:
                return float(os.environ.get(name, "") or default)
            except ValueError:
                return default

        # lease discipline: a claim is renewed every lease_renew
        # seconds; a peer treats a lease unrenewed for lease_ttl as the
        # owner's death.  Renew at ttl/3 so two missed beats still
        # leave slack before anyone steals.
        self.lease_ttl = max(0.2, _fenv("JAXMC_LEASE_TTL", 10.0))
        self.lease_renew = max(0.05, _fenv("JAXMC_LEASE_RENEW",
                                           self.lease_ttl / 3.0))
        # bsig-affinity head start: a NON-affine thief waits this much
        # past expiry before stealing, so the peer whose warm registry
        # already knows the job's layout class wins ties
        self.affinity_grace = max(0.0, _fenv(
            "JAXMC_LEASE_AFFINITY_GRACE",
            min(2.0, self.lease_ttl / 2.0)))
        # cross-daemon poison budget: a job whose owner dies this many
        # times FLEET-WIDE is quarantined, not retried forever
        self.job_retries = max(1, int(_fenv("JAXMC_JOB_RETRIES", 3)))
        # ADMISSION CONTROL (ISSUE 19): bounded spool depth + per-tenant
        # token buckets priced by the analyze-cost fast lane.  Overload
        # answers 429 + Retry-After, never an unbounded queue.
        self.max_depth = max(1, int(_fenv("JAXMC_SERVE_MAX_DEPTH",
                                          1000)))
        self.tenant_burst = max(1.0, _fenv("JAXMC_SERVE_TENANT_BURST",
                                           256.0))
        self.tenant_rate = max(0.01, _fenv("JAXMC_SERVE_TENANT_RATE",
                                           32.0))
        # tenant -> [tokens, last refill time]; guarded by _cv
        self._buckets: Dict[str, List[float]] = {}
        # jids whose lease the fleet thread discovered LOST (stolen
        # while we still run them): their results must not publish
        self._lost: set = set()
        self._fleet_thread: Optional[threading.Thread] = None
        self._fleet_size = 1
        # between jobs the fleet recorder has no span open: that is a
        # daemon waiting for work, not a stall (a job's own watchdog
        # lives where the job runs)
        self.wd = obs.Watchdog(self.tel, idle_ok=True)
        self.metrics_out = metrics_out
        self.host = host
        self.port = port
        self.n_workers = max(1, int(workers))
        # env override so subprocess daemons (the fleet and chaos tests)
        # can tighten the checkpoint cadence takeover resumes ride on
        self.checkpoint_every = _fenv("JAXMC_SERVE_CKPT_EVERY",
                                      checkpoint_every)
        # sig -> {"session": CheckSession, "completed": bool} — the warm
        # kernel registry; "completed" gates checkpoint-replay reuse.
        # Mutated ONLY under _cv (status() snapshots under it too), and
        # each signature additionally serializes its RUNS through
        # _sig_lock: a CheckSession's engine is single-flight state, so
        # two same-signature jobs that dodged batching must not drive
        # it concurrently.
        # BOUNDED LRU (ISSUE 10 satellite, ROADMAP item 3): a
        # long-lived fleet daemon otherwise pins one compiled engine
        # per signature forever.  JAXMC_SERVE_WARM_MAX (default a
        # generous 32) caps the registry; the least-recently-used idle
        # signature is evicted (`serve.evictions` + a `serve.evicted`
        # event), and a re-submission after eviction falls back to the
        # FINAL-CHECKPOINT resume path — bit-identical answer, just
        # cold (the spool checkpoint and the persisted capacity
        # profile survive eviction).
        try:
            self.warm_max = max(1, int(os.environ.get(
                "JAXMC_SERVE_WARM_MAX", "32") or 32))
        except ValueError:
            self.warm_max = 32
        self.warm: Dict[str, Dict[str, Any]] = {}
        self._sig_locks: Dict[str, threading.Lock] = {}
        self._cv = threading.Condition()
        self._pending: collections.deque = collections.deque()
        # jid -> (sig, claim token): the token identifies WHICH claim
        # registered the job, so a worker whose fallback REQUEUED a
        # claimed job (another worker may re-claim it immediately)
        # never pops the re-claimer's live registration in its finally
        self._running: Dict[str, Tuple[str, object]] = {}
        self._draining = False
        self._drain_reason: Optional[str] = None
        self._workers: List[threading.Thread] = []
        self._httpd = None
        self._http_thread: Optional[threading.Thread] = None
        self._jobs_done = 0
        self._jobs_failed = 0
        # CROSS-MODEL VMAPPED BATCHING (ISSUE 13): jobs whose parse-time
        # batch profile (session.batch_profile) puts them in the same
        # layout-compat class (`bsig`) pop TOGETHER and run as ONE
        # vmapped device program (backend/batch.py) — per-job results
        # byte-identical to solo runs, one compile for the cohort.
        # JAXMC_SERVE_BATCH=0 restores exact-signature-only coalescing.
        self.batch_enabled = os.environ.get(
            "JAXMC_SERVE_BATCH", "1").strip().lower() \
            not in ("0", "off", "no", "false")
        try:
            self.batch_max = max(2, int(os.environ.get(
                "JAXMC_SERVE_BATCH_MAX", "8") or 8))
        except ValueError:
            self.batch_max = 8
        # FAST LANE (ROADMAP 1c): analyze's state-space estimate is a
        # pre-scheduling cost oracle — small proven-bounded jobs jump
        # the queue (they finish in milliseconds; parking them behind a
        # multi-minute search is pure latency for free).
        try:
            self.fastlane_bound = int(os.environ.get(
                "JAXMC_SERVE_FASTLANE_BOUND", "50000") or 50000)
        except ValueError:
            self.fastlane_bound = 50000
        # DEVICE-OWNER process — ON BY DEFAULT (ISSUE 19 satellite,
        # ROADMAP 2a): owner death is supervised (requeue + respawn +
        # the cross-daemon retry budget), so device work leaves the
        # daemon process unless JAXMC_SERVE_DEVICE_OWNER=0 opts out.
        # The spawn is lazy: interp-only daemons never pay for it.
        self.owner = None
        if os.environ.get("JAXMC_SERVE_DEVICE_OWNER", "1").strip() \
                .lower() not in ("0", "off", "no", "false"):
            from .owner import DeviceOwner
            self.owner = DeviceOwner(log=self.log)
        self._batch_sigs_seen: set = set()
        # parse-time batch profiles are mtime-cached per (spec, cfg,
        # options): the admission path pays the model load + bounds
        # fixpoint once per content, not once per submission
        self._bprof_cache: Dict[Any, Any] = {}
        # LIVE EXPOSITION (ISSUE 16): jid -> the job's Telemetry while
        # it runs IN THIS PROCESS (GET /metrics per-job series, GET
        # /jobs/<id>/events, /status progress); finished jobs keep
        # their last ring-buffer snapshot in a small bounded LRU.
        # Owner-process jobs have no in-daemon recorder — their events
        # endpoint reads the tail of the job's trace file instead.
        self._job_tels: Dict[str, Any] = {}
        self._done_events: "collections.OrderedDict[str, list]" = \
            collections.OrderedDict()
        self._done_events_max = 16
        # /metrics series TTL hygiene (ISSUE 17): completed jobs keep
        # their {job="<id>"} series (jaxmc_job_running 0 + the final
        # gauges) for JAXMC_METRICS_JOB_TTL seconds after completion,
        # then drop at scrape time — a long-lived fleet no longer grows
        # scrape cardinality with every job it ever ran.  Tests drive
        # expiry by monkeypatching _metrics_clock.
        try:
            self._job_ttl = float(os.environ.get(
                "JAXMC_METRICS_JOB_TTL", "600") or 600)
        except ValueError:
            self._job_ttl = 600.0
        self._metrics_clock = time.time
        # jid -> (completion time, the job's final Telemetry)
        self._done_series: \
            "collections.OrderedDict[str, Tuple[float, Any]]" = \
            collections.OrderedDict()

    # ---- lifecycle ----------------------------------------------------
    def start(self) -> "ServeDaemon":
        # recovery is LEASE-AWARE (ISSUE 19): running jobs still leased
        # by a live peer on the same spool stay theirs; expired ones
        # spend the cross-daemon retry budget (quarantine on exhaustion)
        requeued = self.q.recover(self.daemon_id, ttl=self.lease_ttl,
                                  retries=self.job_retries)
        if requeued:
            self.log(f"serve: requeued {requeued} interrupted job"
                     f"{'s' if requeued != 1 else ''} from the spool")
            self.tel.counter("serve.requeued_on_start", requeued)
        with self._cv:
            for job in sorted(self.q.queued(), key=lambda j: j["id"]):
                self._pending.append(job["id"])
        self._start_http()
        self.q.heartbeat(self.daemon_id, host=self.host,
                         port=self.port, pid=os.getpid())
        self.q.stamp(host=self.host, port=self.port, pid=os.getpid(),
                     workers=self.n_workers, status="serving",
                     daemon=self.daemon_id)
        for wi in range(self.n_workers):
            t = threading.Thread(target=self._worker_loop, args=(wi,),
                                 name=f"jaxmc-serve-w{wi}", daemon=True)
            t.start()
            self._workers.append(t)
        self._fleet_thread = threading.Thread(
            target=self._fleet_loop, name="jaxmc-serve-fleet",
            daemon=True)
        self._fleet_thread.start()
        self.wd.start()
        self._update_gauges()
        self.log(f"serve: listening on http://{self.host}:{self.port} "
                 f"(spool {self.q.root}, {self.n_workers} worker"
                 f"{'s' if self.n_workers != 1 else ''})")
        return self

    def _start_http(self) -> None:
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
        daemon = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *a):  # quiet the default stderr
                pass

            def _json(self, code: int, obj, headers=None) -> None:
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                from .protocol import Overloaded
                from .queue import SpoolDegraded
                try:
                    n = int(self.headers.get("Content-Length") or 0)
                    body = json.loads(self.rfile.read(n).decode()) \
                        if n else {}
                except (ValueError, OSError):
                    return self._json(400, {"error": "bad JSON body"})
                if self.path == "/jobs":
                    try:
                        job = daemon.submit(body)
                    except BadJob as ex:
                        return self._json(400, {"error": str(ex)})
                    except Overloaded as ex:
                        # the 429 contract (ISSUE 19): Retry-After in
                        # the header AND machine-readable gauges in
                        # the body, so clients can back off precisely
                        return self._json(
                            429,
                            dict(ex.body, error=str(ex),
                                 retry_after_s=ex.retry_after_s),
                            headers={"Retry-After": str(max(
                                1, int(round(ex.retry_after_s))))})
                    except SpoolDegraded as ex:
                        # hardened spool writes degrade with a NAMED
                        # verdict, never a raw 500
                        return self._json(
                            503, {"error": str(ex),
                                  "degraded": "spool"})
                    except RuntimeError as ex:  # draining
                        return self._json(503, {"error": str(ex)})
                    return self._json(200, job)
                if self.path == "/drain":
                    daemon.initiate_drain("POST /drain")
                    return self._json(200, {"draining": True})
                return self._json(404, {"error": f"no route {self.path}"})

            def do_GET(self):
                if self.path == "/status":
                    return self._json(200, daemon.status())
                if self.path == "/metrics":
                    # Prometheus text exposition; the snapshot copies
                    # are short-critical-section, so a scraper can poll
                    # aggressively without blocking job threads
                    body = daemon.metrics_text().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if self.path == "/jobs":
                    return self._json(200,
                                      {"jobs": daemon.q.list_jobs()})
                if self.path.startswith("/jobs/"):
                    parts = self.path.split("/")
                    jid = parts[2] if len(parts) > 2 else ""
                    if len(parts) == 4 and parts[3] == "events":
                        evs = daemon.job_events(jid)
                        if evs is None:
                            return self._json(
                                404, {"error": f"no events for {jid}"})
                        return self._json(200, {"job": jid,
                                                "events": evs})
                    if len(parts) == 4 and parts[3] == "result":
                        res = daemon.q.load_result(jid)
                        if res is None:
                            return self._json(
                                404, {"error": f"no result for {jid}"})
                        return self._json(200, res)
                    job = daemon.q.load(jid)
                    if job is None:
                        # quarantined jobs answer with a NAMED verdict
                        # (ISSUE 19): the captured fault context and
                        # trace tail travel with it
                        qrec = daemon.q.load_quarantined(jid)
                        if qrec is not None:
                            return self._json(200, qrec)
                        return self._json(404,
                                          {"error": f"no job {jid}"})
                    if job.get("status") == "done":
                        res = daemon.q.load_result(jid)
                        if res is not None:
                            job = dict(job, result=res.get("result"),
                                       serve=res.get("serve"))
                    return self._json(200, job)
                return self._json(404, {"error": f"no route {self.path}"})

        self._httpd = ThreadingHTTPServer((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever, name="jaxmc-serve-http",
            daemon=True)
        self._http_thread.start()

    def serve_forever(self) -> int:
        """Block until a drain completes; returns the process exit code
        (0 — a drained daemon is a clean daemon)."""
        try:
            while not self._draining:
                time.sleep(0.2)
                self._update_gauges()
        except KeyboardInterrupt:
            self.initiate_drain("KeyboardInterrupt")
        self.shutdown()
        return 0

    def initiate_drain(self, reason: str) -> None:
        """Begin the graceful drain (idempotent): refuse new jobs, ask
        every in-flight engine to checkpoint and stop (jaxmc/drain.py),
        wake idle workers so they exit."""
        with self._cv:
            if self._draining:
                return
            self._draining = True
            self._drain_reason = reason
            self._cv.notify_all()
        drain.request(f"serve drain: {reason}")
        if self.owner is not None:
            # forward to the device-owner process: its engines park at
            # their next safe boundary exactly like in-process ones
            self.owner.drain()
        self.tel.event("serve.drain", reason=reason)
        self.log(f"serve: draining ({reason}) — in-flight jobs will "
                 f"checkpoint and requeue")

    def shutdown(self) -> None:
        """Complete the drain: join workers (their engines return at
        the next safe boundary), stop HTTP, persist the fleet metrics,
        close everything.  No orphan workers, no open spans."""
        if not self._draining:
            self.initiate_drain("shutdown()")
        for t in self._workers:
            t.join(timeout=120.0)
        if self._fleet_thread is not None:
            self._fleet_thread.join(timeout=10.0)
            self._fleet_thread = None
        alive = [t.name for t in self._workers if t.is_alive()]
        if alive:  # never expected: engines poll drain at every level
            self.log(f"serve: WARNING: workers still alive at shutdown: "
                     f"{alive}")
        self._workers = []
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if self._http_thread is not None:
            self._http_thread.join(timeout=5.0)
            self._http_thread = None
        if self.owner is not None:
            self.owner.stop()
        self.wd.stop()
        self._update_gauges()
        # leave the fleet cleanly: a stale heartbeat record would make
        # peers defer submissions to a ghost until it aged out
        self.q.remove_daemon(self.daemon_id)
        self.q.stamp(host=self.host, port=self.port, pid=os.getpid(),
                     workers=self.n_workers, status="stopped",
                     drain_reason=self._drain_reason)
        if self.metrics_out:
            self.tel.write_metrics(
                self.metrics_out,
                result={"ok": True, "distinct": 0, "generated": 0,
                        "diameter": 0, "truncated": False,
                        "jobs_done": self._jobs_done,
                        "jobs_failed": self._jobs_failed,
                        "drain_reason": self._drain_reason})
        self.tel.close()
        # re-arm the process-global drain flag: every engine in this
        # daemon has returned, and an in-process successor daemon (the
        # smoke gate, restart tests) must not inherit a stale request
        drain.clear()

    # ---- admission control (ISSUE 19) ---------------------------------
    def _admit(self, tenant: str, charge: float) -> Tuple[bool, float]:
        """Per-tenant token bucket: `charge` tokens (priced by the
        analyze-cost estimate) or a (False, retry-after) rejection.
        Buckets refill continuously at tenant_rate up to tenant_burst."""
        now = time.time()
        with self._cv:
            b = self._buckets.get(tenant)
            if b is None:
                b = self._buckets[tenant] = [self.tenant_burst, now]
            tokens, last = b
            tokens = min(self.tenant_burst,
                         tokens + (now - last) * self.tenant_rate)
            if tokens >= charge:
                b[0], b[1] = tokens - charge, now
                return True, 0.0
            b[0], b[1] = tokens, now
            return False, (charge - tokens) / self.tenant_rate

    def _reject(self, tenant: str, reason: str, retry_after: float,
                **gauges) -> None:
        self.tel.counter("serve.admission_rejected")
        self.tel.event("serve.admission_rejected", tenant=tenant,
                       reason=reason, **gauges)
        from .protocol import Overloaded
        raise Overloaded(
            f"admission refused ({reason}); retry after "
            f"{retry_after:.1f}s",
            retry_after_s=retry_after,
            body=dict(gauges, tenant=tenant, reason=reason))

    # ---- submission ---------------------------------------------------
    def submit(self, payload: Dict[str, Any]) -> Dict[str, Any]:
        if self._draining:
            raise RuntimeError("daemon is draining; resubmit to the "
                               "next daemon life (the spool persists)")
        tenant = str(payload.get("tenant") or "default")
        with self._cv:
            depth = len(self._pending) + len(self._running)
        if depth >= self.max_depth:
            # bounded spool: overload is a FAST, attributable 429 with
            # the queue gauges in the body — never an unbounded queue
            self._reject(tenant, "queue_full",
                         min(60.0, max(1.0, 0.25 * depth)),
                         queue_depth=depth, max_depth=self.max_depth)
        cfg = build_config(payload.get("spec"), payload.get("cfg"),
                           payload.get("options"))
        # submit-time static analysis (ISSUE 9): a statically-broken
        # spec/cfg pair (cfg names an undefined invariant, unassigned
        # CONSTANTs, unparseable inputs — the linter's error-severity
        # classes) is rejected HERE, before it occupies a worker or
        # enters the durable spool; the 400 payload carries the
        # diagnostics.  JAXMC_SERVE_ANALYZE=0 opts out.
        if os.environ.get("JAXMC_SERVE_ANALYZE", "1").strip().lower() \
                not in ("0", "off", "no", "false"):
            from ..analyze.lint import errors, lint_pair
            errs = errors(lint_pair(cfg.spec, cfg.cfg,
                                    tuple(cfg.include or ()),
                                    semantic=False))
            if errs:
                self.tel.counter("serve.jobs_rejected")
                self.tel.event("serve.job_rejected",
                               spec=cfg.spec,
                               codes=[d.code for d in errs])
                raise BadJob(
                    "statically broken job rejected by the analyzer: "
                    + "; ".join(d.render() for d in errs[:5]))
        sig = job_signature(cfg)
        # parse-time batch profile (ISSUE 13): the layout-compat class
        # key + analyze's cost estimate, both computed BEFORE any
        # engine exists; a failure here only means the job schedules
        # solo, exactly as before
        bsig = cost = None
        fast = False
        if self.batch_enabled and cfg.backend != "interp":
            # mtime-keyed cache: the profile costs a model load + the
            # bounds fixpoint — pay it once per (spec, cfg, options)
            # content, not once per submission on the admission path
            try:
                key = (cfg.spec, cfg.cfg,
                       os.path.getmtime(cfg.spec),
                       os.path.getmtime(cfg.cfg) if cfg.cfg else None,
                       json.dumps(cfg.batch_signature_fields(),
                                  sort_keys=True))
            except OSError:
                key = None
            if key is not None and key in self._bprof_cache:
                prof = self._bprof_cache[key]
            else:
                from ..session import batch_profile
                try:
                    prof = batch_profile(cfg)
                except Exception:  # noqa: BLE001 — profiling must
                    prof = None    # never reject a servable job
                if key is not None:
                    if len(self._bprof_cache) >= 256:
                        self._bprof_cache.clear()
                    self._bprof_cache[key] = prof
            if prof is not None:
                bsig, cost = prof.bsig, prof.cost_estimate
                fast = cost is not None and cost <= self.fastlane_bound
        # token-bucket admission, PRICED by the fast-lane cost oracle:
        # proven-small jobs are cheap, estimate-heavy ones cost up to
        # 4 tokens, unpriced jobs cost 1 — so a tenant's burst budget
        # is spent in proportion to the work it schedules
        charge = 1.0
        if cost is not None:
            charge = 0.25 if fast else min(
                4.0, 1.0 + cost / (4.0 * self.fastlane_bound))
        ok, wait_s = self._admit(tenant, charge)
        if not ok:
            self._reject(tenant, "tenant_rate",
                         max(0.1, wait_s), queue_depth=depth,
                         cost_estimate=cost, charge=charge)
        job = self.q.new_job(cfg.spec, cfg.cfg, payload.get("options"),
                             sig, bsig=bsig, cost_estimate=cost,
                             fast_lane=fast or None, tenant=tenant)
        self.tel.counter("serve.jobs_submitted")
        # WARM-HIT ROUTING (ISSUE 19): on a multi-daemon spool, a job
        # whose signature is NOT warm here stays spool-only — a peer
        # whose warm registry knows it adopts it immediately from its
        # fleet scan, everyone else (including us) only after the
        # affinity grace.  Single-daemon spools enqueue locally always.
        with self._cv:
            sig_warm = sig in self.warm
        if not fast and not sig_warm and self._fleet_size > 1:
            self.tel.counter("serve.jobs_deferred")
            with self._cv:
                self._cv.notify()
            self._update_gauges()
            return job
        with self._cv:
            if fast:
                # proven-small jobs jump the queue (fast lane)
                self._pending.appendleft(job["id"])
                self.tel.counter("serve.fastlane_jobs")
            else:
                self._pending.append(job["id"])
            if bsig:
                self._batch_sigs_seen.add(bsig)
                self.tel.gauge("serve.batch_sigs",
                               len(self._batch_sigs_seen))
            self._cv.notify()
        self._update_gauges()
        return job

    # ---- the fleet thread (ISSUE 19) -----------------------------------
    def _fleet_loop(self) -> None:
        """Heartbeat + lease renewal + spool scan, one thread.  The
        `lease_stall` fault site freezes a whole tick (no heartbeat, no
        renewals) so tests can force a live daemon's leases to expire
        and prove the double-claim arbitration."""
        from .. import faults
        interval = max(0.05, min(self.lease_renew, 1.0))
        while not self._draining:
            if faults.fire("lease_stall", daemon=self.daemon_id):
                self.tel.counter("serve.lease_stalls")
                time.sleep(interval)
                continue
            try:
                self._fleet_tick()
            except Exception as ex:  # noqa: BLE001 — the fleet thread
                # must outlive any one bad spool read
                self.tel.event("serve.fleet_tick_error", error=str(ex))
            time.sleep(interval)

    def _fleet_tick(self) -> None:
        self.q.heartbeat(self.daemon_id, host=self.host,
                         port=self.port, pid=os.getpid(),
                         running=len(self._running),
                         warm=len(self.warm))
        self._fleet_size = max(1, len(self.q.daemons(self.lease_ttl)))
        # renew every lease we hold; a failed renewal means a peer
        # stole the job (our stall outlived the TTL) — the run paths
        # check _lost before publishing anything
        with self._cv:
            held = list(self._running)
        for jid in held:
            if self.q.renew(jid, self.daemon_id):
                continue
            with self._cv:
                if jid not in self._running:
                    continue  # finished+released between snapshot/renew
            cur = self.q.lease(jid)
            if cur is None and self.q.try_claim(
                    jid, self.daemon_id, self.lease_ttl):
                continue  # lease file vanished; re-established
            with self._cv:
                if jid in self._lost:
                    continue
                self._lost.add(jid)
            self.tel.counter("serve.lease_lost")
            self.tel.event("serve.lease_lost", id=jid,
                           thief=(cur or {}).get("daemon"))
            self.log(f"serve: lease on {jid} LOST to "
                     f"{(cur or {}).get('daemon')} — its result will "
                     f"be discarded here")
        self._scan_spool()

    def _scan_spool(self) -> None:
        """Adopt spool work this daemon does not know about: queued
        jobs other daemons deferred (bsig-affinity routing) and running
        jobs whose lease expired (crash takeover).  Affine daemons —
        signature warm here, or the layout class already run here —
        move first; everyone else waits out the affinity grace."""
        now = time.time()
        with self._cv:
            known = set(self._pending) | set(self._running)
            warm_sigs = set(self.warm)
            bsigs = set(self._batch_sigs_seen)
        adopted = []
        for job in self.q.list_jobs():
            jid = job["id"]
            if jid in known:
                continue
            status = job.get("status")
            affine = job.get("sig") in warm_sigs or \
                (job.get("bsig") and job.get("bsig") in bsigs) or \
                bool(job.get("fast_lane"))
            if status == "queued":
                age = now - float(job.get("submitted_at") or 0)
                if affine or age > self.affinity_grace or \
                        self._fleet_size <= 1:
                    adopted.append(jid)
                    if affine:
                        self.tel.counter("serve.affinity_adoptions")
            elif status == "running":
                cur = self.q.lease(jid)
                expired = cur is None or cur["age"] > self.lease_ttl
                if not expired:
                    continue
                if not affine and cur is not None and \
                        cur["age"] <= self.lease_ttl + \
                        self.affinity_grace:
                    continue  # give an affine thief the head start
                out = self.q.takeover(jid, self.daemon_id,
                                      self.lease_ttl, self.job_retries)
                if out == "requeued":
                    self.tel.counter("serve.takeovers")
                    self.tel.event("serve.takeover", id=jid,
                                   dead=(cur or {}).get("daemon"))
                    self.log(f"serve: took over {jid} from dead peer "
                             f"{(cur or {}).get('daemon')} (lease "
                             f"expired; resuming from its checkpoint)")
                    adopted.append(jid)
        if adopted:
            with self._cv:
                for jid in adopted:
                    if jid not in self._pending and \
                            jid not in self._running:
                        self._pending.append(jid)
                self._cv.notify_all()
            self.tel.counter("serve.jobs_adopted", len(adopted))
            self._update_gauges()

    def _still_owned(self, jid: str) -> bool:
        """May THIS daemon publish the job's result?  False once the
        fleet thread saw the lease stolen, or the spool says another
        daemon holds it now."""
        with self._cv:
            if jid in self._lost:
                return False
        return self.q.owns(jid, self.daemon_id)

    def _publishable(self, jobs: List[Dict[str, Any]]) -> \
            List[Dict[str, Any]]:
        """Filter a finished claim down to the members whose lease we
        still hold; dropped members were stolen mid-run (the thief's
        re-run is the publication of record — exactly one winner)."""
        out = []
        for j in jobs:
            if self._still_owned(j["id"]):
                out.append(j)
            else:
                self.tel.counter("serve.lease_lost_drops")
                self.tel.event("serve.lease_lost_drop", id=j["id"])
        return out

    # ---- workers ------------------------------------------------------
    def _worker_loop(self, wi: int) -> None:
        while True:
            with self._cv:
                while not self._pending and not self._draining:
                    self._cv.wait(0.5)
                if self._draining:
                    return  # queued jobs persist for the next life
                jid = self._pending.popleft()
                job = self.q.load(jid)
                if job is not None and job.get("status") != "queued":
                    # finished/claimed through the shared spool by a
                    # peer daemon while it sat in our local deque
                    job = None
                if job is not None and not self.q.try_claim(
                        jid, self.daemon_id, self.lease_ttl):
                    job = None  # a peer holds a live lease on it
                followers: List[Dict[str, Any]] = []
                xmembers: List[Dict[str, Any]] = []
                if job is not None:
                    # BATCH: claim every queued job with this signature
                    # (one engine run answers all of them) AND — when
                    # the leader carries a batch profile — every job in
                    # the same LAYOUT-COMPAT class (`bsig`): those run
                    # as one vmapped device program (ISSUE 13).
                    # Claiming happens under the ONE _cv hold that also
                    # registers every claimed id in _running, so a
                    # second worker popping the same signature class
                    # can never pick a claimed follower up again (the
                    # satellite race), and the LRU eviction's busy-set
                    # sees every claimed signature.
                    bsig = job.get("bsig") if self.batch_enabled \
                        else None
                    xsigs = {job["sig"]}
                    rest = []
                    for other in self._pending:
                        oj = self.q.load(other)
                        if oj is None:
                            rest.append(other)
                        elif oj.get("status") != "queued":
                            continue  # a peer already took it; drop
                        elif oj.get("sig") == job["sig"]:
                            if self.q.try_claim(other, self.daemon_id,
                                                self.lease_ttl):
                                followers.append(oj)
                            # claim lost to a peer: drop from our deque
                        elif bsig and oj.get("bsig") == bsig and \
                                (oj.get("sig") in xsigs or
                                 len(xsigs) < self.batch_max) and \
                                (not job.get("fast_lane") or
                                 oj.get("fast_lane")) and \
                                self.q.try_claim(other, self.daemon_id,
                                                 self.lease_ttl):
                            # a fast-lane leader claims only fast-lane
                            # members: stapling a proven-small job to a
                            # multi-minute cohort member would withhold
                            # its result for the whole cohort wall —
                            # the inversion the lane exists to prevent
                            xmembers.append(oj)
                            xsigs.add(oj["sig"])
                        else:
                            rest.append(other)
                    self._pending = collections.deque(rest)
                    tok = object()  # this claim's ownership marker
                    self._running[jid] = (job["sig"], tok)
                    for j in followers + xmembers:
                        self._running[j["id"]] = (j["sig"], tok)
            if job is None:
                continue
            claimed = followers + xmembers
            try:
                if xmembers:
                    self._run_vbatch(job, followers, xmembers)
                elif self.owner is not None and \
                        (job.get("options") or {}).get(
                            "backend", "interp") != "interp":
                    # owner mode: solo DEVICE jobs leave the daemon
                    # process too (interp jobs stay on the thread pool)
                    self._run_owner_solo(job, followers)
                else:
                    self._run_batch(job, followers)
            except Exception as ex:  # noqa: BLE001 — a job failure must
                # never kill the worker; the defect lands on the job —
                # but only on jobs THIS claim still owns (a fallback
                # may have requeued some, and another worker may
                # already be running them)
                with self._cv:
                    own = self._running.get(job["id"])
                    leader_owned = own is not None and own[1] is tok
                    still = [
                        j for j in claimed
                        if (self._running.get(j["id"])
                            or (None, None))[1] is tok]
                err = f"{type(ex).__name__}: {ex}"
                if leader_owned:
                    self._fail_job(job, still, err)
                elif still:
                    # the leader itself was requeued (and possibly
                    # re-claimed elsewhere): fail only the members this
                    # claim still owns
                    self._fail_job(still[0], still[1:], err)
            finally:
                mine = []
                with self._cv:
                    for j in [job] + claimed:
                        cur = self._running.get(j["id"])
                        if cur is not None and cur[1] is tok:
                            self._running.pop(j["id"])
                            mine.append(j["id"])
                    self._lost.difference_update(
                        j["id"] for j in [job] + claimed)
                # drop the leases this claim still holds — requeued
                # members released theirs when they were handed back
                for mj in mine:
                    self.q.release(mj, self.daemon_id)
                self._update_gauges()

    def _fail_job(self, job, followers, error: str) -> None:
        self.tel.counter("serve.jobs_failed", 1 + len(followers))
        self._jobs_failed += 1 + len(followers)
        self.tel.event("serve.job_failed", id=job["id"], error=error)
        self.log(f"serve: job {job['id']} FAILED: {error}")
        for j in [job] + followers:
            self.q.mark(j["id"], "failed", error=error,
                        finished_at=time.time(),
                        batch_leader=job["id"]
                        if j is not job else None)

    def _requeue_or_quarantine(self, members: List[Dict[str, Any]],
                               note: str) -> None:
        """Hand crashed-owner jobs back to the fleet: each spends one
        unit of its CROSS-DAEMON retry budget and requeues; a member
        whose budget is gone is a poison job and quarantines with the
        fault context instead (ISSUE 19 tentpole 3)."""
        with self._cv:
            for j in members:
                attempt = self.q.spend_retry(j["id"], self.job_retries)
                if attempt is None:
                    self._running.pop(j["id"], None)
                    self.q.quarantine(
                        j["id"],
                        f"poison job: owner died {self.job_retries} "
                        f"times across the fleet (cross-daemon retry "
                        f"budget exhausted)",
                        context={"note": note,
                                 "daemon": self.daemon_id})
                    continue
                self.q.mark(j["id"], "queued",
                            requeue_note=f"{note} (attempt {attempt}/"
                                         f"{self.job_retries})")
                self.q.release(j["id"], self.daemon_id)
                self._running.pop(j["id"], None)
                self._pending.append(j["id"])
            self._cv.notify_all()

    def _sig_lock(self, sig: str) -> threading.Lock:
        with self._cv:
            lk = self._sig_locks.get(sig)
            if lk is None:
                lk = self._sig_locks[sig] = threading.Lock()
            return lk

    def _locked_sig(self, sig: str):
        """Per-signature run lock, IMMUNE to the LRU-eviction race
        (ISSUE 13 bugfix): eviction pops a sig's lock from the registry,
        and a worker that FETCHED the lock object before the eviction
        but ACQUIRED it after would no longer serialize against a later
        worker's fresh lock — two jobs could then drive one warm
        session's single-flight engine concurrently.  Re-fetch after
        acquiring and retry until the held object IS the registered
        one."""
        import contextlib

        @contextlib.contextmanager
        def _cm():
            while True:
                lk = self._sig_lock(sig)
                lk.acquire()
                with self._cv:
                    if self._sig_locks.get(sig) is lk:
                        break
                lk.release()
            try:
                yield
            finally:
                lk.release()

        return _cm()

    def _touch_warm_locked(self, sig: str) -> None:
        """Move `sig` to the registry's most-recently-used end (dicts
        are insertion-ordered; caller holds _cv)."""
        entry = self.warm.pop(sig, None)
        if entry is not None:
            self.warm[sig] = entry

    def _evict_warm_locked(self) -> None:
        """Evict least-recently-used IDLE signatures past warm_max
        (caller holds _cv).  A signature mid-run (claimed in _running
        or its per-sig lock held) is never evicted — the next-oldest
        idle one goes instead."""
        if len(self.warm) <= self.warm_max:
            return
        busy = {s for s, _t in self._running.values()}
        for sig in list(self.warm):
            if len(self.warm) <= self.warm_max:
                break
            if sig in busy:
                continue
            lk = self._sig_locks.get(sig)
            if lk is not None and lk.locked():
                continue
            del self.warm[sig]
            self._sig_locks.pop(sig, None)
            self.tel.counter("serve.evictions")
            self.tel.event("serve.evicted", sig=sig)
            self.log(f"serve: evicted warm session {sig[:12]} "
                     f"(LRU, warm_max={self.warm_max}; resubmission "
                     f"resumes its final checkpoint cold)")

    def _revalidate_profile(self, sess: CheckSession, job_tel) -> None:
        """Warm-path consistency check: confirm the DURABLE capacity
        profile still matches the warm engine's layout before trusting
        its caps (counts as a profile hit in the job's artifact; a
        missing/stale profile only means the next cold engine re-learns
        — the warm engine's own caps stay valid)."""
        if sess.layout_sig and sess.model is not None:
            from ..compile.cache import load_capacity_profile
            # profiles are namespaced by backend platform (ISSUE 11):
            # ask the warm engine's descriptor for the variant the
            # profile was saved under
            desc = getattr(sess.engine, "backend_desc", None)
            variant = desc.profile_variant() if desc is not None else ""
            load_capacity_profile(sess.model.module.name,
                                  sess.layout_sig, tel=job_tel,
                                  variant=variant)

    def _job_trace_path(self, jid: str) -> str:
        """The job's JSONL trace artifact (next to its result JSON) —
        one lane of the fleet's `obs timeline` view."""
        return os.path.join(self.q.results_dir, f"{jid}.trace.jsonl")

    def _register_job_tel(self, jids: List[str], job_tel) -> None:
        with self._cv:
            for j in jids:
                self._job_tels[j] = job_tel

    def _unregister_job_tel(self, jids: List[str], job_tel) -> None:
        """Drop the live registration; the leader keeps its final ring
        snapshot in the bounded done-LRU so /jobs/<id>/events stays
        answerable briefly after completion."""
        with self._cv:
            now = self._metrics_clock()
            for j in jids:
                if self._job_tels.get(j) is job_tel:
                    del self._job_tels[j]
                # TTL-retained /metrics series (ISSUE 17): scrapes keep
                # rendering the finished job's final series (running 0)
                # until the TTL prunes it at scrape time
                self._done_series[j] = (now, job_tel)
                self._done_series.move_to_end(j)
            if jids:
                self._done_events[jids[0]] = job_tel.recent_events()
                self._done_events.move_to_end(jids[0])
                while len(self._done_events) > self._done_events_max:
                    self._done_events.popitem(last=False)

    def _register_done_artifact(self, jids: List[str],
                                summary: Dict[str, Any]) -> None:
        """TTL-retained /metrics series for owner-run jobs: the live
        recorder finished in the owner process, so render the final
        series from the shipped artifact (same prune window as the
        in-daemon path's _unregister_job_tel)."""
        series = _ArtifactSeries(summary)
        with self._cv:
            now = self._metrics_clock()
            for j in jids:
                self._done_series[j] = (now, series)
                self._done_series.move_to_end(j)

    def _publish_job(self, j: Dict[str, Any], summary: Dict[str, Any],
                     status: str, claimed_at: float,
                     sent: Optional[Dict[str, float]] = None,
                     **fields) -> None:
        """One job's artifact and final record, with its STATIONS
        (serve/protocol.py "A job's clock"): the record's own, the
        owner request's (`sent`: DeviceOwner.request's, None where no
        owner ran the job) and the two the owner stamped into its
        summary, and beside them in the `serve` block the differences
        an operator wants without arithmetic.  A station the job did
        not pass is left out, never defaulted.  The artifact is written
        twice: `finished_at` is stamped AFTER its write, as it always
        was, `publish_s` holds that write, and the second carries
        both."""
        sv = dict(summary.get("serve") or {})
        art = dict(summary, serve=sv)
        st = {k: j.get(k) for k in ("submitted_at", "enqueued_at")}
        st["claimed_at"] = claimed_at
        owner = sv.pop("stations", None)
        if sent is not None:
            st.update(owner or {}, **sent)
        self.q.save_result(j["id"], art)
        st["finished_at"] = time.time()
        sv["stations"] = {k: st[k] for k in STATIONS
                          if st.get(k) is not None}
        if sent is not None:
            sv["owner_wait_s"] = round(
                st["owner_sent_at"] - claimed_at, 6)
            sv["owner_envelope_s"] = round(
                st["owner_received_at"] - st["owner_sent_at"]
                - (sv.get("job_wall_s") or 0.0), 6)
            sv["publish_s"] = round(
                st["finished_at"] - st["owner_received_at"], 6)
            if "owner_spawned_at" in st and "owner_began_at" in st:
                # the child's coming up, from the process started to
                # its first job begun: what the spawn put into this
                # job's `owner_envelope_s`
                sv["owner_spawn_s"] = round(
                    st["owner_began_at"] - st["owner_spawned_at"], 6)
        self.q.save_result(j["id"], art)
        self.q.mark(j["id"], status, **fields,
                    **{k: v for k, v in sv["stations"].items()
                       if k != "submitted_at"})

    def _run_batch(self, job: Dict[str, Any],
                   followers: List[Dict[str, Any]]) -> None:
        jid, sig = job["id"], job["sig"]
        cfg = build_config(job["spec"], job.get("cfg"),
                           job.get("options"))
        if cfg.backend == "interp" and not cfg.workers:
            # daemon parallelism comes from the WORKER POOL (several
            # jobs at once), not per-job fork pools: forking from a
            # multithreaded daemon risks classic fork+locks hangs, so
            # interp jobs default to the serial engine unless the
            # submission explicitly asks for a worker count (note both
            # None and 0 mean "auto" on the CLI surface — neither may
            # reach default_workers() here)
            cfg.workers = 1
        ck = self.q.ckpt_path(sig)
        cfg.checkpoint = ck
        cfg.checkpoint_every = self.checkpoint_every
        cfg.final_checkpoint = True
        job_tel = obs.Telemetry(
            trace_path=self._job_trace_path(jid),
            meta={"command": "serve.job", "job": jid, "sig": sig,
                  "backend": cfg.backend, "spec": job["spec"],
                  "cfg": job.get("cfg"), "env": obs.environment_meta()})
        # per-JOB watchdog (ISSUE 16): the stall threshold derives from
        # THIS job's level rhythm — concurrent tenants no longer share
        # one threshold built from their mixed median level wall
        jwd = obs.Watchdog(job_tel)
        jids = [j["id"] for j in [job] + followers]
        self._register_job_tel(jids, job_tel)
        jwd.start()
        try:
            self._run_batch_inner(job, followers, cfg, ck, job_tel)
        finally:
            jwd.stop()
            self._unregister_job_tel(jids, job_tel)

    def _run_batch_inner(self, job: Dict[str, Any],
                         followers: List[Dict[str, Any]],
                         cfg, ck: str, job_tel) -> None:
        jid, sig = job["id"], job["sig"]
        t0 = time.time()
        for j in [job] + followers:
            self.q.mark(j["id"], "running", started_at=t0,
                        claimed_at=t0, daemon=self.daemon_id,
                        batch_leader=jid if j is not job else None)
        if followers:
            self.tel.counter("serve.batched_jobs", len(followers))
        self._update_gauges()
        from .. import faults
        faults.kill_self("daemon_kill", job=jid, kind="solo",
                         spec=os.path.basename(job["spec"]))

        with self._cv:
            warm = self.warm.get(sig)
            if warm is not None:
                self._touch_warm_locked(sig)
        # the warm/replay decision below mirrors owner.run_solo's, which
        # is the reference (the owner is the default device path): a
        # completed entry AND its finalized checkpoint on disk
        warm_engine = resumed = False
        with self._locked_sig(sig), obs.use_local(job_tel), \
                self.tel.span("job", id=jid, sig=sig, spec=job["spec"],
                              backend=cfg.backend,
                              batched=len(followers)):
            if warm is not None and warm.get("completed") and \
                    os.path.exists(ck):
                # WARM: the already-compiled engine replays the
                # finalized checkpoint — zero recompiles, instant answer
                warm_engine = resumed = True
                self.tel.counter("serve.warm_hits")
                sess = warm["session"]
                # rebind the session's telemetry channel to THIS job's
                # recorder (it was constructed with the cold job's, long
                # closed): the warm artifact must carry its own search
                # span like any other jaxmc.metrics summary
                sess.tel = job_tel
                sess.log = obs.Logger(job_tel, quiet=True)
                self._revalidate_profile(sess, job_tel)
                res = sess.explore(resume_from=ck, checkpoint_path=ck,
                                   final_checkpoint=True)
            else:
                self.tel.counter("serve.cold_runs")
                if os.path.exists(ck):
                    # a previous daemon life checkpointed this signature
                    # (periodic, drain, or final): resume incrementally
                    cfg.resume = ck
                    resumed = True
                    self.tel.counter("serve.ckpt_resumes")
                sess = CheckSession(cfg, tel=job_tel,
                                    log=obs.Logger(job_tel, quiet=True))
                if sess.parse() == "assumes":
                    raise BadJob(
                        "assumes-mode specs (no behavior spec) are not "
                        "servable; run them via `python -m jaxmc check`")
                try:
                    sess.compile()
                    res = sess.explore()
                except (RuntimeError, OSError, MemoryError,
                        ConnectionError) as ex:
                    if cfg.backend == "interp":
                        raise
                    # the CLI's device->CPU fallback, same policy
                    # (session.demote_to_cpu is the shared path: it
                    # re-raises unless a host snapshot exists)
                    res = sess.demote_to_cpu(ex)
                with self._cv:
                    self.warm[sig] = {"session": sess,
                                      "completed": False}
                    self._evict_warm_locked()

        drained = bool(getattr(res, "drained", False))
        completed = res.ok and not res.truncated and not drained
        with self._cv:
            if sig in self.warm:
                # checkpoint-replay reuse only for COMPLETED searches
                # (the final checkpoint exists exactly then); other
                # outcomes still keep the warm kernels for the next
                # submission
                self.warm[sig]["completed"] = completed or \
                    self.warm[sig].get("completed", False)

        # the job artifact: a normal jaxmc.metrics/2 summary + the
        # serve block (obs/schema.py PR-7 notes)
        window_recompiles = sum(1 for lv in job_tel.levels
                                if lv.get("fresh_compile"))
        wall = time.time() - t0
        result_block: Dict[str, Any] = {
            "ok": res.ok, "distinct": res.distinct,
            "generated": res.generated, "diameter": res.diameter,
            "truncated": bool(res.truncated),
            "wall_s": round(res.wall_s, 6),
            "finished_on": sess.finished_on,
            "warnings": list(getattr(res, "warnings", []))}
        if drained:
            result_block["drained"] = True
        if res.violation is not None:
            from ..engine.explore import format_trace
            result_block["violation"] = {"kind": res.violation.kind,
                                         "name": res.violation.name}
            result_block["trace"] = format_trace(res.violation)
        summary = job_tel.summary(result=result_block)
        summary["backend"] = cfg.backend
        summary["spec"] = job["spec"]
        summary["serve"] = {
            "sig": sig, "warm_engine": warm_engine,
            "resumed_from_checkpoint": resumed,
            "window_recompiles": window_recompiles,
            "profile_hits": job_tel.counters.get("profile.hits", 0),
            "persistent_cache_hits": job_tel.counters.get(
                "compile.persistent_cache_hits", 0),
            "program_hits": job_tel.counters.get(
                "compile.program_hits", 0),
            "batched_with": [f["id"] for f in followers],
            "job_wall_s": round(wall, 6),
        }
        job_tel.close()
        # run ledger (ISSUE 17): one trajectory point per batch (the
        # leader's summary IS every member's summary); never raises
        try:
            from ..obs.ledger import append_summary
            append_summary(summary, source=job["spec"])
        except Exception:  # noqa: BLE001
            pass

        status = "drained" if drained else "done"
        publish = self._publishable([job] + followers)
        if not publish:
            return  # every member was stolen mid-run; the thief answers
        for j in publish:
            self._publish_job(
                j, summary, status, t0,
                ok=res.ok, distinct=res.distinct,
                generated=res.generated, warm_engine=warm_engine,
                resumed_from_checkpoint=resumed,
                window_recompiles=window_recompiles,
                daemon=self.daemon_id,
                batch_leader=jid if j is not job else None)
        if drained:
            self.tel.counter("serve.jobs_drained", len(publish))
            self.log(f"serve: job {jid} drained at a safe boundary "
                     f"(checkpointed; will resume next life)")
        else:
            self.tel.counter("serve.jobs_done", len(publish))
            self._jobs_done += len(publish)
            self.log(f"serve: job {jid} done in {wall:.2f}s "
                     f"(ok={res.ok}, {res.distinct} distinct, "
                     f"warm={warm_engine}, resumed={resumed}, "
                     f"batched={len(followers)})")

    def _run_owner_solo(self, job: Dict[str, Any],
                        followers: List[Dict[str, Any]]) -> None:
        """One solo device job (plus exact-sig followers) in the
        device-owner process.  The in-process warm registry does not
        apply — the signature-keyed spool checkpoint still makes
        repeats incremental (the owner resumes it) — and an owner death
        requeues the jobs exactly like a mid-batch death."""
        t0 = time.time()
        jid, sig = job["id"], job["sig"]
        jobs = [job] + followers
        for j in jobs:
            # `started_at` is the worker's CLAIM (`claimed_at`, the same
            # instant): the owner may be busy with another worker's job
            # for a long while yet (`owner_wait_s` says how long)
            self.q.mark(j["id"], "running", started_at=t0,
                        claimed_at=t0, daemon=self.daemon_id,
                        batch_leader=jid if j is not job else None)
        if followers:
            self.tel.counter("serve.batched_jobs", len(followers))
        self._update_gauges()
        from .. import faults
        faults.kill_self("daemon_kill", job=jid, kind="solo",
                         spec=os.path.basename(job["spec"]))
        sent: Dict[str, float] = {}
        md = {"spec": job["spec"], "cfg": job.get("cfg"),
              "options": job.get("options"), "sig": sig,
              "jids": [j["id"] for j in jobs],
              "checkpoint": self.q.ckpt_path(sig),
              "checkpoint_every": self.checkpoint_every,
              "trace": self._job_trace_path(jid)}
        from .owner import OwnerDied
        with self.tel.span("job", id=jid, sig=sig, spec=job["spec"],
                           owner=True, batched=len(followers)):
            try:
                resp = self.owner.request(
                    {"kind": "solo", "member": md}, stations=sent,
                    tel=self.tel)
            except OwnerDied as ex:
                if ex.timed_out:
                    # policy kill: requeueing would livelock (the
                    # re-run hits the same deadline) — the timeout is
                    # the job's verdict
                    self._fail_job(job, followers, str(ex))
                    return
                self.tel.counter("serve.owner_respawns")
                self.tel.event("serve.owner_died", error=str(ex))
                self.log(f"serve: device-owner died mid-job ({ex}); "
                         f"requeued {len(jobs)} job"
                         f"{'s' if len(jobs) != 1 else ''}")
                self._requeue_or_quarantine(
                    jobs, f"requeued after device-owner death: {ex}")
                return
        if resp.get("error"):
            self._fail_job(job, followers, resp["error"])
            return
        summary = resp["summary"]
        sv = summary.setdefault("serve", {})
        sv["cost_estimate"] = job.get("cost_estimate")
        # the owner's own warm registry reports warmth now (ISSUE 19:
        # owner is the default device path, so the warm/cold/resume
        # counters must not go dark when work leaves the daemon)
        warm_engine = bool(sv.get("warm_engine"))
        resumed = bool(sv.get("resumed_from_checkpoint"))
        if warm_engine:
            self.tel.counter("serve.warm_hits")
        else:
            self.tel.counter("serve.cold_runs")
            if resumed:
                self.tel.counter("serve.ckpt_resumes")
        status = "drained" if resp.get("drained") else "done"
        publish = self._publishable(jobs)
        if not publish:
            return  # stolen mid-run; the thief's re-run answers
        for j in publish:
            self._publish_job(
                j, summary, status, t0, sent,
                ok=resp["ok"], distinct=resp["distinct"],
                generated=resp["generated"],
                warm_engine=warm_engine, device_owner=True,
                resumed_from_checkpoint=resumed,
                daemon=self.daemon_id,
                batch_leader=jid if j is not job else None)
        self._register_done_artifact([j["id"] for j in publish],
                                     summary)
        if status == "drained":
            self.tel.counter("serve.jobs_drained", len(publish))
            self.log(f"serve: job {jid} drained in the device owner "
                     f"(checkpointed; will resume next life)")
        else:
            self.tel.counter("serve.jobs_done", len(publish))
            self._jobs_done += len(publish)
            self.log(f"serve: job {jid} done in the device owner "
                     f"({time.time() - t0:.2f}s, ok={resp['ok']}, "
                     f"{resp['distinct']} distinct, "
                     f"warm={warm_engine}, resumed={resumed})")

    # ---- cross-model vmapped batches (ISSUE 13) ------------------------
    def _run_vbatch(self, job: Dict[str, Any],
                    followers: List[Dict[str, Any]],
                    xmembers: List[Dict[str, Any]]) -> None:
        """Run one layout-compat cohort — the leader (+ its exact-sig
        followers) and every claimed cross-model member — through ONE
        vmapped device program.  Per-job artifacts and statuses are
        written exactly like solo runs; on any cohort-level failure the
        cross-model members are REQUEUED and the leader falls back to
        the solo path, so batching can delay a job but never lose or
        corrupt one."""
        t0 = time.time()
        jid = job["id"]
        # one member per DISTINCT signature; duplicates share a result
        groups: Dict[str, List[Dict[str, Any]]] = \
            {job["sig"]: [job] + followers}
        order = [job["sig"]]
        for oj in xmembers:
            if oj["sig"] not in groups:
                groups[oj["sig"]] = []
                order.append(oj["sig"])
            groups[oj["sig"]].append(oj)
        # BATCH-SCOPED CHECKPOINTS (ISSUE 19 tentpole 4): each member
        # checkpoints under a bsig-scoped key (the merged batch layout
        # has its own lane plan — the solo `ckpt/<sig>.ck` would refuse
        # to resume it), so a drained or stolen cohort RE-FORMS from
        # per-member checkpoints instead of restarting solo
        bsig = job.get("bsig") or "solo"
        desc = [{"spec": groups[s][0]["spec"],
                 "cfg": groups[s][0].get("cfg"),
                 "options": groups[s][0].get("options"),
                 "sig": s, "bsig": job.get("bsig"),
                 "jids": [j["id"] for j in groups[s]],
                 "checkpoint": self.q.batch_ckpt_path(bsig, s),
                 "checkpoint_every": self.checkpoint_every,
                 "trace": self._job_trace_path(groups[s][0]["id"])}
                for s in order]
        for s in order:
            for j in groups[s]:
                self.q.mark(j["id"], "running", started_at=t0,
                            claimed_at=t0, daemon=self.daemon_id,
                            batch_leader=jid
                            if j["id"] != jid else None,
                            bsig=job.get("bsig"))
        self.tel.counter("serve.vbatch_jobs",
                         sum(len(groups[s]) for s in order))
        self._update_gauges()
        from .. import faults
        faults.kill_self("daemon_kill", job=jid, kind="vbatch",
                         spec=os.path.basename(job["spec"]))

        def _requeue(members: List[Dict[str, Any]], note: str,
                     strip_bsig: bool = False) -> None:
            # strip_bsig: a DETERMINISTIC batch failure (compat refused
            # at build) must not re-form the same failing cohort — the
            # retry runs solo; transient failures (owner death) keep
            # the bsig so the retry can batch again
            with self._cv:
                for j in members:
                    self.q.mark(j["id"], "queued", requeue_note=note,
                                bsig=None if strip_bsig
                                else j.get("bsig"))
                    self.q.release(j["id"], self.daemon_id)
                    self._running.pop(j["id"], None)
                    self._pending.append(j["id"])
                self._cv.notify_all()

        resp = None
        # the owner request's stations: None where the cohort runs in
        # this process, whose jobs then carry no owner station at all
        sent: Optional[Dict[str, float]] = \
            {} if self.owner is not None else None
        with self.tel.span("vbatch", id=jid, bsig=job.get("bsig"),
                           members=len(order),
                           jobs=sum(len(groups[s]) for s in order)):
            if self.owner is not None:
                from .owner import OwnerDied
                try:
                    resp = self.owner.request(
                        {"kind": "vbatch", "members": desc},
                        stations=sent, tel=self.tel)
                except OwnerDied as ex:
                    if ex.timed_out:
                        # policy kill, not a death: requeueing would
                        # re-run the identical cohort into the same
                        # deadline forever — fail with the named knob
                        self._fail_job(job, followers + xmembers,
                                       str(ex))
                        return
                    # the owner process died with the cohort in flight:
                    # nothing was written, so every job simply requeues
                    # and the next device job respawns the owner
                    self.tel.counter("serve.owner_respawns")
                    self.tel.event("serve.owner_died", error=str(ex))
                    self.log(f"serve: device-owner died mid-batch "
                             f"({ex}); requeued "
                             f"{sum(len(groups[s]) for s in order)} "
                             f"jobs")
                    # an owner DEATH spends the cross-daemon retry
                    # budget; members keep their bsig so the cohort
                    # re-forms and resumes its batch checkpoints
                    self._requeue_or_quarantine(
                        [j for s in order for j in groups[s]],
                        f"requeued after device-owner death: {ex}")
                    return
            else:
                from .owner import run_vbatch
                resp = run_vbatch(desc)

        if resp.get("error"):
            # owner-side cohort-level failure (not a death — the child
            # answered): deterministic, so requeueing would loop; the
            # REAL error lands on every job
            self._fail_job(job, followers + xmembers, resp["error"])
            return
        if resp.get("incompatible"):
            # parse-time bsig said compatible but the build disagreed
            # (e.g. a lifted constant reached a static-only position):
            # cross-model members requeue solo, the leader group runs
            # the ordinary path
            self.tel.counter("serve.batch_incompatible")
            self.log(f"serve: batch {job.get('bsig')} fell back to "
                     f"solo runs ({resp['incompatible']})")
            _requeue(xmembers, "requeued after batch-compat fallback: "
                               + str(resp["incompatible"]),
                     strip_bsig=True)
            self._run_batch(job, followers)
            return

        occupancy = int(resp.get("occupancy") or 0)
        self.tel.gauge("serve.batch_occupancy", occupancy)
        # MEASURED by the batch engine (1 by construction today; a
        # future in-cohort rebuild would surface here, not be papered
        # over by a constant)
        self.tel.gauge("serve.batch_compiles",
                       int(resp.get("engine_builds") or 1))
        done = failed = drained_n = 0
        for md, mres in zip(desc, resp["members"]):
            jobs = groups[md["sig"]]
            if mres.get("retry_solo"):
                # engine-level abort solo runs recover from (adaptive
                # relayout): requeue WITH BATCHING STRIPPED so the
                # retry cannot re-form the same failing cohort
                self.tel.counter("serve.batch_solo_retries", len(jobs))
                self.log(f"serve: batch member {md['jids'][0]} "
                         f"requeued for solo retry "
                         f"({mres['retry_solo']})")
                with self._cv:
                    for j in jobs:
                        self.q.mark(j["id"], "queued", bsig=None,
                                    requeue_note="solo retry: "
                                    + str(mres["retry_solo"]))
                        self._running.pop(j["id"], None)
                        self._pending.append(j["id"])
                    self._cv.notify_all()
                continue
            if mres.get("error"):
                self.tel.counter("serve.jobs_failed", len(jobs))
                self._jobs_failed += len(jobs)
                self.tel.event("serve.job_failed", id=md["jids"][0],
                               error=mres["error"])
                for j in jobs:
                    self.q.mark(j["id"], "failed", error=mres["error"],
                                finished_at=time.time(),
                                batch_leader=jid
                                if j["id"] != jid else None)
                failed += len(jobs)
                continue
            summary = mres["summary"]
            sv = summary.setdefault("serve", {})
            sv["cost_estimate"] = jobs[0].get("cost_estimate")
            # where the cohort ran, as a solo job's block says it
            # (`run_vbatch` is one runner for both places)
            sv["device_owner"] = self.owner is not None
            resumed = bool(sv.get("resumed_from_checkpoint"))
            status = "drained" if mres.get("drained") else "done"
            publish = self._publishable(jobs)
            for j in publish:
                self._publish_job(
                    j, summary, status, t0, sent,
                    ok=mres["ok"], distinct=mres["distinct"],
                    generated=mres["generated"], warm_engine=False,
                    device_owner=self.owner is not None,
                    resumed_from_checkpoint=resumed,
                    batch_occupancy=occupancy, daemon=self.daemon_id,
                    batch_leader=jid if j["id"] != jid else None)
            self._register_done_artifact([j["id"] for j in publish],
                                         summary)
            if status == "drained":
                drained_n += len(publish)
            else:
                done += len(publish)
                self._jobs_done += len(publish)
        if drained_n:
            self.tel.counter("serve.jobs_drained", drained_n)
        if done:
            self.tel.counter("serve.jobs_done", done)
        self.log(f"serve: vbatch {jid} done in "
                 f"{time.time() - t0:.2f}s (members={len(order)}, "
                 f"occupancy={occupancy}, done={done}, "
                 f"failed={failed}, drained={drained_n})")

    # ---- introspection ------------------------------------------------
    def _update_gauges(self) -> None:
        with self._cv:
            depth = len(self._pending)
            running = len(self._running)
        self.tel.gauge("serve.queue_depth", depth)
        self.tel.gauge("serve.running", running)
        self.tel.gauge("serve.warm_sessions", len(self.warm))
        self.tel.gauge("serve.workers", self.n_workers)
        self.tel.gauge("serve.draining", self._draining)
        # serve.fleet gauges (ISSUE 19; schema note in obs/schema.py)
        self.tel.gauge("serve.fleet_daemons", self._fleet_size)
        self.tel.gauge("serve.leases_held", running)

    def job_events(self, jid: str) -> Optional[list]:
        """Recent trace events for one job, readable MID-RUN: the live
        ring buffer for in-daemon jobs, the trace-file tail for
        owner-process jobs, the retained ring for recently finished
        ones.  None when nothing is known about the job."""
        with self._cv:
            jt = self._job_tels.get(jid)
            done = self._done_events.get(jid)
        if jt is not None:
            return jt.recent_events()
        if done is not None:
            return list(done)
        try:  # owner-process jobs: their Telemetry streams to the
            # spool trace file, flushed per event — tail it
            with open(self._job_trace_path(jid),
                      encoding="utf-8") as fh:
                lines = fh.readlines()[-256:]
            out = []
            for ln in lines:
                try:
                    out.append(json.loads(ln))
                except ValueError:
                    pass  # torn final line of a live writer
            return out
        except OSError:
            return None

    def metrics_text(self) -> str:
        """The GET /metrics body: Prometheus text exposition 0.0.4 over
        the fleet counters/gauges plus per-running-job series labeled
        {job="<id>"} (name grammar in obs/schema.py).  Built from
        short-critical-section snapshots — never blocks job threads."""
        self._update_gauges()
        fleet = self.tel.metrics_snapshot()
        with self._cv:
            jobs = dict(self._job_tels)
        # family name -> (type, [(label_str, value)])
        fams: Dict[str, Tuple[str, list]] = {}

        def add(name, value, typ="gauge", jid=None, site=None):
            if isinstance(value, bool):
                value = int(value)
            if not isinstance(value, (int, float)):
                return
            fam = fams.setdefault(obs.prom_name(name), (typ, []))
            if jid is None:
                lbl = ""
            else:
                pairs = ['job="%s"' % str(jid).replace('"', "'")]
                if site is not None:
                    pairs.append('site="%s"'
                                 % str(site).replace('"', "'"))
                lbl = "{%s}" % ",".join(pairs)
            fam[1].append((lbl, value))

        def add_prof(jid, jt):
            # per-dispatch-site gauges plus the MEASURED device peak
            # (absent where the backend reports none; the PROCESS's
            # peak, so the same under every job's label), straight off
            # the job recorder's always-on profiler
            prof = getattr(jt, "prof", None)
            if prof is None:
                return
            for sname, st in sorted(prof.sites.items()):
                add("prof.site_dispatches", st.dispatches,
                    jid=jid, site=sname)
                if st.wall_s:
                    add("prof.site_wall_s", round(st.wall_s, 6),
                        jid=jid, site=sname)
            peak = prof.hbm_peak_bytes
            if peak:
                add("hbm.peak_bytes", peak, jid=jid)

        for name, v in fleet["counters"].items():
            add(name, v, "counter")
        for name, v in fleet["gauges"].items():
            add(name, v, "gauge")
        now = time.time()
        seen_tels = set()
        for jid, jt in sorted(jobs.items()):
            if id(jt) in seen_tels:
                continue  # followers share the leader's recorder
            seen_tels.add(id(jt))
            add("job.running", 1, jid=jid)
            snap = jt.metrics_snapshot()
            for gname, gval in snap["gauges"].items():
                add(gname, gval, jid=jid)
            if snap["levels"]:
                add("job.levels", len(snap["levels"]), jid=jid)
            gen = sum(lv.get("generated") or 0
                      for lv in snap["levels"])
            wall = max(now - jt.t_start, 1e-9)
            if gen:
                add("job.states_per_sec", round(gen / wall, 3),
                    jid=jid)
            pe = jt.progress_est
            if pe is not None:
                ps = pe.snapshot()
                add("job.progress_distinct", ps["distinct"], jid=jid)
                if ps["eta_s"] is not None:
                    add("job.progress_eta_s", ps["eta_s"], jid=jid)
            add_prof(jid, jt)
        # completed jobs linger for JAXMC_METRICS_JOB_TTL seconds so a
        # scraper on a coarse interval still sees the final series of a
        # short job (ISSUE 17 satellite: bounded by TTL, not forever)
        mnow = self._metrics_clock()
        with self._cv:
            for jid in [j for j, (t, _jt) in self._done_series.items()
                        if mnow - t > self._job_ttl]:
                del self._done_series[jid]
            done = [(jid, jt) for jid, (t, jt)
                    in self._done_series.items()
                    if jid not in jobs]
        for jid, jt in done:
            add("job.running", 0, jid=jid)
            snap = jt.metrics_snapshot()
            for gname, gval in snap["gauges"].items():
                add(gname, gval, jid=jid)
            if snap["levels"]:
                add("job.levels", len(snap["levels"]), jid=jid)
            add_prof(jid, jt)
        lines = []
        for name in sorted(fams):
            typ, samples = fams[name]
            lines.append(f"# TYPE {name} {typ}")
            for lbl, value in samples:
                lines.append(f"{name}{lbl} {value}")
        return "\n".join(lines) + "\n"

    def status(self) -> Dict[str, Any]:
        self._update_gauges()
        # ONE snapshot hold for every shared map (ISSUE 19 satellite):
        # the /metrics TTL pruner deletes done-job series under _cv at
        # scrape time, so rendering the per-job progress block must
        # work from copies taken in the same critical section — never
        # iterate a live map the pruner can mutate mid-iteration
        with self._cv:
            pending = list(self._pending)
            running = {jid: s for jid, (s, _t)
                       in self._running.items()}
            warm = {s: w["session"] for s, w in self.warm.items()}
            job_tels = dict(self._job_tels)
            done_series = [(jid, jt) for jid, (_t, jt)
                           in self._done_series.items()]
        # live per-job search progress (ISSUE 16): fraction/ETA from
        # the job's estimator, `unbounded` when analyze offered none —
        # recently-done jobs keep their final snapshot until the TTL
        # prunes them
        progress = {}
        for jid, jt in job_tels.items():
            pe = jt.progress_est
            if pe is not None:
                progress[jid] = pe.snapshot()
        for jid, jt in done_series:
            pe = jt.progress_est
            if jid not in progress and pe is not None:
                progress[jid] = dict(pe.snapshot(), done=True)
        return {
            "progress": progress,
            "spool": self.q.root,
            "queue_depth": len(pending),
            "pending": pending,
            "running": running,
            "fleet": {"daemon_id": self.daemon_id,
                      "daemons": self._fleet_size,
                      "lease_ttl": self.lease_ttl,
                      "lease_renew": self.lease_renew,
                      "job_retries": self.job_retries},
            "quarantined": len(self.q.quarantined()),
            "batch_enabled": self.batch_enabled,
            "device_owner_pid": self.owner.pid
            if self.owner is not None else None,
            # one process per chip: with the owner on, THIS process
            # must never hold a jax backend (the owner's init would
            # fail against an exclusive accelerator)
            "daemon_holds_device": obs.live_devices() is not None,
            "warm_sessions": {
                s: sess.describe() for s, sess in warm.items()},
            "workers": self.n_workers,
            "draining": self._draining,
            "jobs_done": self._jobs_done,
            "jobs_failed": self._jobs_failed,
            "counters": dict(self.tel.counters),
            "gauges": dict(self.tel.gauges),
        }
