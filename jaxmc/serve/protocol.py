r"""The serve job protocol: JSON over HTTP, plus the tiny stdlib client.

Endpoints (all JSON bodies/responses; the daemon binds 127.0.0.1):

  POST /jobs          {spec, cfg?, options?{...check options...},
                       tenant?}
                      -> 200 {id, sig, status}  |  400 bad job
                      |  429 admission refused (queue full or the
                         tenant's token bucket is dry): Retry-After
                         header + {error, retry_after_s, reason,
                         queue_depth/…gauges} body — the client backs
                         off and resubmits, nothing was enqueued
                      |  503 daemon is draining, or the spool
                         degraded ({degraded: "spool"}) after
                         exhausting write retries
  GET  /jobs          -> {jobs: [job records]}
  GET  /jobs/<id>     -> job record (+ "result" summary once done)
  GET  /jobs/<id>/result
                      -> the job's full jaxmc.metrics/3 artifact
                         (result block carries ok/counts/violation and
                         the rendered counterexample trace), 404 before
                         completion
  GET  /jobs/<id>/events
                      -> {id, events: [...]} — the job's bounded
                         in-memory trace-event ring (JAXMC_TRACE_RING,
                         default 256), readable MID-RUN; falls back to
                         the persisted per-job trace tail after the
                         daemon forgets the ring; 404 when neither
                         exists
  GET  /metrics       -> Prometheus text format 0.0.4 (fleet counters
                         and gauges as jaxmc_serve_*, per-job series
                         labeled {job="<id>"} incl. the live
                         jaxmc_search_progress_est fraction); never
                         blocks job threads
  GET  /status        -> {queue_depth, running, warm_sessions, workers,
                          draining, counters, gauges, progress}
  POST /drain         -> initiate the graceful drain (same path as
                         SIGTERM); 200 {draining: true}

A job record: {id, sig, status: queued|running|done|failed|drained|
quarantined, submitted_at, started_at?, finished_at?, spec, cfg,
options, batch_leader?, error?, tenant?, daemon?, stolen_by?} and the
other STATIONS the job has passed so far ("A job's clock" below).
`daemon` names the fleet member that ran (or is running) the job;
`stolen_by` appears after a lease-expiry takeover.  A QUARANTINED job
(its owner died JAXMC_JOB_RETRIES times across the fleet) answers
GET /jobs/<id> with the quarantine record: the named verdict, the
captured fault context, and the trace tail at death.

The `serve` block of a served job's artifact (GET /jobs/<id>/result):

  sig, bsig?               the job's signature (and batch class)
  warm_engine              answered by an already-built engine: the
                           replay of its finalized checkpoint
  resumed_from_checkpoint  the search started from a checkpoint (a warm
                           replay, a previous life's, a takeover)
  device_owner             ran in the device-owner child
  job_wall_s               the run's wall where it ran (the owner's
                           `run_solo`, config to summary)
  window_recompiles        levels flagged `fresh_compile` (a NEW engine
                           flags its first dispatch, compiled or loaded
                           from the persistent cache: `prof.programs[]
                           .origin` says which; not where the process
                           held the program already, origin `held`)
  profile_hits, persistent_cache_hits   counters of the job's recorder
  program_hits             programs this job's engine took from the
                           process's registry instead of tracing,
                           lowering and loading them again
                           (`compile.program_hits`, compile/cache.py):
                           an edit that left the model unchanged
  batched_with             ids answered by the same run
  cost_estimate            analyze's state-space estimate, if any
  stations                 the job's wall-clock marks (`STATIONS`, below),
                           the same numbers its record carries
  owner_wait_s             `owner_sent_at - claimed_at`: a CLAIMED job
                           waiting for the device owner (the other
                           worker's whole job or cohort, a spawn)
  owner_envelope_s         `owner_received_at - owner_sent_at -
                           job_wall_s`: what the request cost beside the
                           run (pipe both ways, pickling, the summary)
  publish_s                `finished_at - owner_received_at`: the
                           artifact's write
  owner_spawn_s            only where the request spawned the owner:
                           `owner_began_at - owner_spawned_at`, the child
                           coming up (it is inside `owner_envelope_s`)

A job's clock: the STATIONS, `time.time()` marks of one host in the order
a job passes them, each in the record (GET /jobs/<id>) under its name and
all in the artifact's `serve.stations`; a station the job did not pass is
ABSENT, never zero.

  submitted_at       daemon   the record is made (`queue.new_job`; lint,
                              signature and batch profile lie before it)
  enqueued_at        daemon   the spool's hard write of it is down
  claimed_at         daemon   a worker claimed the job and marked it
                              `running`: `started_at`, same instant, kept
                              under its old name
  owner_spawned_at   daemon   only in the request that had to spawn the
                              owner: its process is started
  owner_sent_at      daemon   `DeviceOwner.request` holds the lock and a
                              live owner: the request enters the pipe
  owner_began_at     owner    `run_solo` / `run_vbatch` begins (`t0`)
  owner_ended_at     owner    ... and ends: `job_wall_s` is their
                              difference
  owner_received_at  daemon   the answer is out of the pipe
  finished_at        daemon   after the artifact's write (the artifact
                              is then written once more, to carry it)

`started_at` is the worker's CLAIM, not the run's start: with more workers
than owners a `running` job may not have begun — the owner is busy with
another worker's job — and `owner_wait_s` says for how long.  Followers
and the members of a vbatch carry their leader's owner stations; a job the
daemon's own thread answered (`JAXMC_SERVE_DEVICE_OWNER=0`, an interp job
and its replay) has the daemon's four and no `owner_*` key.  `python -m
jaxmc.obs report` prints them as one `stations:` line.  The same borders
are SPANS on the clock a device trace has: in the daemon's recorder the
`job` / `vbatch` span holds `job.owner_wait` (the lock wanted -> held)
and `job.owner_run` (into the pipe -> out of it); in the owner ONE
envelope span a job, `job` or `vbatch`, on the job's (the leader's) own
recorder, so its artifact's `phases` and the owner's trace carry it.

Job SIGNATURES (`job_signature`) hash the spec/cfg CONTENTS plus every
result-affecting option (session.SessionConfig.job_signature_fields),
so "identical job" means identical model and identical search — the
key under which checkpoints persist, warm sessions are reused, and
queued duplicates batch through one dispatch.  Editing the spec file
changes the signature and invalidates all of that, by construction.

Options accepted in a submission are the check-surface subset below
(`OPTION_FIELDS`); checkpoint/resume/telemetry paths are daemon-owned
and rejected if submitted.
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Any, Dict, Optional

from ..session import SessionConfig, default_cfg_path, read_text

# the submission-settable option surface (everything else in
# SessionConfig is daemon-owned plumbing)
OPTION_FIELDS = (
    "backend", "platform", "max_states", "workers", "no_deadlock",
    "seq_cap", "grow_cap", "kv_cap", "no_trace", "host_seen", "sample",
    "chunk", "resident", "include", "progress_every", "res_caps",
    "por",
)

JOB_STATUSES = ("queued", "running", "done", "failed", "drained",
                "quarantined")

#: a served job's stations in the order it passes them ("A job's clock"
#: above): keys of the record and of the artifact's `serve.stations`
STATIONS = ("submitted_at", "enqueued_at", "claimed_at",
            "owner_spawned_at", "owner_sent_at", "owner_began_at",
            "owner_ended_at", "owner_received_at", "finished_at")


class BadJob(ValueError):
    """A submission the daemon refuses; the message is the 400 body."""


class Overloaded(RuntimeError):
    """Admission control refused the submission (bounded spool depth or
    a dry per-tenant token bucket).  Carries the machine-readable
    backoff: the HTTP layer renders 429 + Retry-After + the queue/cost
    gauges in `body`, so clients can distinguish 'fleet is full' from
    'you specifically are over budget'."""

    def __init__(self, msg: str, retry_after_s: float = 1.0,
                 body: Optional[Dict[str, Any]] = None):
        super().__init__(msg)
        self.retry_after_s = float(retry_after_s)
        self.body = dict(body or {})


def build_config(spec: str, cfg: Optional[str],
                 options: Optional[Dict[str, Any]]) -> SessionConfig:
    """Validate a submission into a SessionConfig (checkpoint fields
    left for the daemon to fill).  Raises BadJob with the defect."""
    if not spec or not isinstance(spec, str):
        raise BadJob("job needs a 'spec' path")
    if not os.path.isfile(spec):
        raise BadJob(f"spec not found on the daemon's filesystem: {spec}")
    if cfg is not None and not os.path.isfile(cfg):
        raise BadJob(f"cfg not found on the daemon's filesystem: {cfg}")
    options = dict(options or {})
    unknown = sorted(set(options) - set(OPTION_FIELDS))
    if unknown:
        raise BadJob(f"unknown/forbidden job options: {unknown} "
                     f"(accepted: {sorted(OPTION_FIELDS)})")
    kw: Dict[str, Any] = {}
    for k in OPTION_FIELDS:
        if k in options and options[k] is not None:
            kw[k] = options[k]
    if "sample" in kw:
        kw["sample"] = tuple(kw["sample"])
    if "include" in kw:
        kw["include"] = tuple(kw["include"])
    try:
        return SessionConfig(spec=spec, cfg=cfg, **kw)
    except TypeError as ex:
        raise BadJob(f"bad job options: {ex}")


def job_signature(cfg: SessionConfig) -> str:
    """The warm-reuse / checkpoint / batching key: spec+cfg CONTENT
    hashes plus the result-affecting option surface."""
    effective_cfg = cfg.cfg or default_cfg_path(cfg.spec)
    ident = dict(cfg.job_signature_fields())
    ident["spec_sha"] = hashlib.sha256(
        read_text(cfg.spec).encode()).hexdigest()
    ident["cfg_sha"] = hashlib.sha256(
        read_text(effective_cfg).encode()).hexdigest() \
        if effective_cfg else None
    blob = json.dumps(ident, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


# --------------------------------------------------------------- client

class ServeClient:
    """Minimal stdlib HTTP client for the daemon (tests, the submit/
    status subcommands, the make serve-check smoke)."""

    def __init__(self, host: str, port: int, timeout: float = 30.0):
        self.host = host
        self.port = port
        self.timeout = timeout
        # response headers of the LAST request (Retry-After on a 429)
        self.last_headers: Dict[str, str] = {}

    @classmethod
    def from_spool(cls, spool: str, timeout: float = 30.0
                   ) -> "ServeClient":
        """Discover a live daemon from its spool's serve.json stamp."""
        with open(os.path.join(spool, "serve.json"),
                  encoding="utf-8") as fh:
            info = json.load(fh)
        return cls(info["host"], info["port"], timeout)

    def _request(self, method: str, path: str,
                 body: Optional[Dict[str, Any]] = None):
        import urllib.request
        import urllib.error
        url = f"http://{self.host}:{self.port}{path}"
        data = json.dumps(body).encode() if body is not None else None
        req = urllib.request.Request(
            url, data=data, method=method,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req,
                                        timeout=self.timeout) as resp:
                self.last_headers = dict(resp.headers.items())
                return resp.status, json.loads(resp.read().decode())
        except urllib.error.HTTPError as ex:
            self.last_headers = dict(ex.headers.items()) \
                if ex.headers is not None else {}
            try:
                return ex.code, json.loads(ex.read().decode())
            except Exception:  # noqa: BLE001 — non-JSON error body
                return ex.code, {"error": str(ex)}

    def submit(self, spec: str, cfg: Optional[str] = None,
               options: Optional[Dict[str, Any]] = None,
               tenant: Optional[str] = None):
        body = {"spec": spec, "cfg": cfg, "options": options or {}}
        if tenant is not None:
            body["tenant"] = tenant
        return self._request("POST", "/jobs", body)

    def job(self, jid: str):
        return self._request("GET", f"/jobs/{jid}")

    def result(self, jid: str):
        return self._request("GET", f"/jobs/{jid}/result")

    def status(self):
        return self._request("GET", "/status")

    def drain(self):
        return self._request("POST", "/drain")

    def wait(self, jid: str, timeout: float = 300.0,
             poll_s: float = 0.2) -> Dict[str, Any]:
        """Poll until the job leaves the queue; returns the final job
        record.  Raises TimeoutError with the last-seen status."""
        import time
        deadline = time.time() + timeout
        last = {}
        while time.time() < deadline:
            code, last = self.job(jid)
            if code == 200 and last.get("status") in (
                    "done", "failed", "drained", "quarantined"):
                return last
            time.sleep(poll_s)
        raise TimeoutError(
            f"job {jid} still {last.get('status')!r} after {timeout}s")
