r"""The serve daemon's durable on-disk job queue (the spool).

Layout under one root directory:

    <spool>/serve.json           live-daemon stamp {host, port, pid, ...}
    <spool>/jobs/<id>.json       one job record per file (atomic writes)
    <spool>/results/<id>.json    the job's jaxmc.metrics/2 artifact
    <spool>/ckpt/<sig>.ck        checkpoints, keyed by job SIGNATURE so
                                 identical jobs share one resume ladder
                                 (serve/protocol.py defines signatures)
    <spool>/daemons/<id>.json    fleet membership: one heartbeat record
                                 per live daemon (ISSUE 19)
    <spool>/leases/<id>.lease    per-job lease: which daemon owns the
                                 job right now, renewed by heartbeat
    <spool>/retries/<id>.r<k>    cross-daemon retry latches (O_EXCL)
    <spool>/quarantine/<id>.json poison jobs parked with fault context

Durability contract: every mutation is a whole-file atomic write
(tmp + os.replace, the obs.write_json_atomic pattern), so a SIGKILLed
daemon leaves a readable spool.  `recover()` runs at daemon start:
jobs stuck in `running` whose lease has EXPIRED (the owning daemon
died mid-job) and jobs a drain parked as `drained` go back to
`queued` — their signature-keyed checkpoint (periodic, drain, or
final) lets the next run resume instead of re-exploring.  Jobs still
leased by a live peer are left alone.  Job IDs are monotonic per
spool (`<spool>/.seq`, under an O_EXCL-free fcntl lock) so queue
order survives restarts and sorts lexicographically.

Fleet contract (ISSUE 19): a job claim is a LEASE, not a mutex — the
lease file carries the owning daemon id and a generation counter, and
its mtime is the renewal clock.  Stealing an expired lease is
arbitrated by an O_EXCL generation latch (`<id>.lease.steal.g<n>`,
the faults.py budget-latch pattern) so exactly one thief wins even
when several peers notice the expiry in the same tick.  Requeues
after an owner death spend a CROSS-DAEMON retry budget (`retries/`
latches); when it is exhausted the job is quarantined instead of
re-poisoning the fleet.

Spool I/O hardening: job/result writes pass through `_write_hard`,
which retries transient failures (and the injected `spool_io_error`
fault site) with exponential backoff, then degrades with a named
`serve.spool_degraded` event + `SpoolDegraded` instead of a raw 500.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

from .. import faults
from ..obs import write_json_atomic

#: spool-write retry policy (satellite a): attempts and base backoff
SPOOL_WRITE_TRIES = 3
SPOOL_WRITE_BACKOFF_S = 0.05


class SpoolDegraded(RuntimeError):
    """A spool write failed even after retries — the daemon answers
    with a NAMED 503 (never a raw 500) and keeps serving what it can."""

    def __init__(self, path: str, err: str):
        super().__init__(
            f"spool degraded: cannot write {os.path.basename(path)}: {err}")
        self.path = path
        self.err = err


class JobQueue:
    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.jobs_dir = os.path.join(self.root, "jobs")
        self.results_dir = os.path.join(self.root, "results")
        self.ckpt_dir = os.path.join(self.root, "ckpt")
        self.daemons_dir = os.path.join(self.root, "daemons")
        self.leases_dir = os.path.join(self.root, "leases")
        self.retries_dir = os.path.join(self.root, "retries")
        self.quarantine_dir = os.path.join(self.root, "quarantine")
        for d in (self.jobs_dir, self.results_dir, self.ckpt_dir,
                  self.daemons_dir, self.leases_dir, self.retries_dir,
                  self.quarantine_dir):
            os.makedirs(d, exist_ok=True)
        # optional telemetry hook (the owning daemon sets it) so spool
        # retries/degrades surface as serve.* counters + events
        self.tel = None

    # ---- ids ----------------------------------------------------------
    def _next_id(self) -> str:
        """Monotonic job id, crash-safe across daemon restarts: the
        counter file is read-modify-written under an exclusive flock."""
        seq_path = os.path.join(self.root, ".seq")
        fd = os.open(seq_path, os.O_CREAT | os.O_RDWR, 0o644)
        try:
            try:
                import fcntl
                fcntl.flock(fd, fcntl.LOCK_EX)
            except (ImportError, OSError):
                pass  # single-daemon spools stay correct without it
            raw = os.read(fd, 32)
            n = int(raw) if raw.strip() else 0
            n += 1
            os.lseek(fd, 0, os.SEEK_SET)
            os.ftruncate(fd, 0)
            os.write(fd, str(n).encode())
            return f"j{n:08d}"
        finally:
            os.close(fd)

    # ---- hardened writes ----------------------------------------------
    def _write_hard(self, path: str, obj: Dict[str, Any]) -> None:
        """Atomic JSON write with transient-failure retries.  The
        `spool_io_error` fault site injects failures here (ctx:
        file=<basename>); real OSErrors take the same path.  After
        SPOOL_WRITE_TRIES the write degrades with a named event."""
        last = None
        for attempt in range(SPOOL_WRITE_TRIES):
            try:
                if faults.fire("spool_io_error",
                               file=os.path.basename(path)):
                    raise OSError("injected spool_io_error")
                write_json_atomic(path, obj)
                if attempt and self.tel is not None:
                    self.tel.counter("serve.spool_retries", attempt)
                return
            except OSError as ex:
                last = ex
                time.sleep(SPOOL_WRITE_BACKOFF_S * (2 ** attempt))
        if self.tel is not None:
            self.tel.counter("serve.spool_degraded")
            self.tel.event("serve.spool_degraded",
                           file=os.path.basename(path), error=str(last))
        raise SpoolDegraded(path, str(last))

    # ---- job records --------------------------------------------------
    def job_path(self, jid: str) -> str:
        return os.path.join(self.jobs_dir, f"{jid}.json")

    def result_path(self, jid: str) -> str:
        return os.path.join(self.results_dir, f"{jid}.json")

    def trace_path(self, jid: str) -> str:
        return os.path.join(self.results_dir, f"{jid}.trace.jsonl")

    def ckpt_path(self, sig: str) -> str:
        return os.path.join(self.ckpt_dir, f"{sig}.ck")

    def batch_ckpt_path(self, bsig: str, sig: str) -> str:
        """Per-member checkpoint of a vbatch cohort.  Keyed by BOTH the
        batch class and the member signature: the merged batch layout
        has a different lane plan than the solo layout, so these can
        never share `ckpt/<sig>.ck` (the resume guard would refuse)."""
        return os.path.join(self.ckpt_dir, f"b{bsig}.{sig}.ck")

    def new_job(self, spec: str, cfg: Optional[str], options: Dict,
                sig: str, **extra) -> Dict[str, Any]:
        """`extra` carries scheduler metadata (ISSUE 13): `bsig` (the
        layout-compat batch class), `cost_estimate` (analyze's
        state-space estimate) and `fast_lane` — all optional and
        omitted when absent, so old spools read unchanged.  ISSUE 19
        adds `tenant` (admission accounting) the same way."""
        job = {
            "id": self._next_id(), "sig": sig, "status": "queued",
            "submitted_at": time.time(), "spec": spec, "cfg": cfg,
            "options": dict(options or {}),
        }
        job.update({k: v for k, v in extra.items() if v is not None})
        self.save(job)
        # the station after `submitted_at` (serve/protocol.py): the
        # spool's hard write — its retries and backoff included — is
        # down, the record durable; kept in the record itself, for the
        # worker that claims the job reads it from there
        job["enqueued_at"] = time.time()
        self.save(job)
        return job

    def save(self, job: Dict[str, Any]) -> None:
        self._write_hard(self.job_path(job["id"]), job)

    def load(self, jid: str) -> Optional[Dict[str, Any]]:
        try:
            with open(self.job_path(jid), encoding="utf-8") as fh:
                return json.load(fh)
        except (OSError, ValueError):
            return None

    def mark(self, jid: str, status: str, **fields) -> Dict[str, Any]:
        job = self.load(jid) or {"id": jid}
        job["status"] = status
        job.update(fields)
        self.save(job)
        return job

    def list_jobs(self) -> List[Dict[str, Any]]:
        out = []
        try:
            names = sorted(os.listdir(self.jobs_dir))
        except OSError:
            return out
        for name in names:
            if not name.endswith(".json"):
                continue
            j = self.load(name[:-len(".json")])
            if j is not None:
                out.append(j)
        return out

    def queued(self) -> List[Dict[str, Any]]:
        return [j for j in self.list_jobs() if j.get("status") == "queued"]

    # ---- results ------------------------------------------------------
    def save_result(self, jid: str, summary: Dict[str, Any]) -> None:
        self._write_hard(self.result_path(jid), summary)

    def load_result(self, jid: str) -> Optional[Dict[str, Any]]:
        try:
            with open(self.result_path(jid), encoding="utf-8") as fh:
                return json.load(fh)
        except (OSError, ValueError):
            return None

    # ---- daemon registry ----------------------------------------------
    def daemon_path(self, daemon_id: str) -> str:
        return os.path.join(self.daemons_dir, f"{daemon_id}.json")

    def heartbeat(self, daemon_id: str, **info) -> None:
        """Refresh this daemon's fleet-membership record.  Peers treat
        a record older than the daemon TTL as a dead node."""
        try:
            write_json_atomic(self.daemon_path(daemon_id),
                              dict(info, id=daemon_id, t=time.time()))
        except OSError:
            pass  # a missed heartbeat is recoverable; the next isn't far

    def remove_daemon(self, daemon_id: str) -> None:
        try:
            os.unlink(self.daemon_path(daemon_id))
        except OSError:
            pass

    def daemons(self, ttl: float) -> List[Dict[str, Any]]:
        """Fleet members with a heartbeat younger than `ttl` seconds.
        Liveness is judged by the record's OWN clock stamp falling
        inside the window — a SIGKILLed daemon simply ages out."""
        out = []
        now = time.time()
        try:
            names = sorted(os.listdir(self.daemons_dir))
        except OSError:
            return out
        for name in names:
            if not name.endswith(".json"):
                continue
            try:
                with open(os.path.join(self.daemons_dir, name),
                          encoding="utf-8") as fh:
                    rec = json.load(fh)
            except (OSError, ValueError):
                continue
            if now - float(rec.get("t", 0)) <= ttl:
                out.append(rec)
        return out

    # ---- leases --------------------------------------------------------
    def lease_path(self, jid: str) -> str:
        return os.path.join(self.leases_dir, f"{jid}.lease")

    def _read_lease(self, jid: str) -> Optional[Dict[str, Any]]:
        path = self.lease_path(jid)
        try:
            age = time.time() - os.stat(path).st_mtime
        except OSError:
            return None
        try:
            with open(path, encoding="utf-8") as fh:
                rec = json.load(fh)
        except (OSError, ValueError):
            # mid-write or corrupt: the mtime still dates it, and a
            # generation of 0 makes any steal latch race correctly
            rec = {}
        rec.setdefault("daemon", None)
        rec.setdefault("gen", 0)
        rec["age"] = age
        return rec

    def lease(self, jid: str) -> Optional[Dict[str, Any]]:
        return self._read_lease(jid)

    def try_claim(self, jid: str, daemon_id: str,
                  ttl: float) -> bool:
        """Claim the job's lease.  First claim is an O_EXCL create;
        re-claim by the current holder is a renewal; an EXPIRED lease
        (no renewal for > ttl) may be stolen — the steal of generation
        g is arbitrated by an O_EXCL latch on `<lease>.steal.g<g+1>`,
        so exactly one thief wins no matter how many peers race."""
        path = self.lease_path(jid)
        payload = {"job": jid, "daemon": daemon_id, "gen": 1,
                   "t": time.time()}
        try:
            fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY,
                         0o644)
            with os.fdopen(fd, "w", encoding="utf-8") as fh:
                json.dump(payload, fh)
            return True
        except FileExistsError:
            pass
        except OSError:
            return False
        cur = self._read_lease(jid)
        if cur is None:
            # vanished between EXCL-fail and read: retry once
            return self.try_claim(jid, daemon_id, ttl)
        if cur["daemon"] == daemon_id:
            return self.renew(jid, daemon_id)
        if cur["age"] <= ttl:
            return False  # held by a live peer
        # expired: race for the generation latch
        gen = int(cur.get("gen", 0)) + 1
        latch = f"{path}.steal.g{gen}"
        try:
            os.close(os.open(latch,
                             os.O_CREAT | os.O_EXCL | os.O_WRONLY,
                             0o644))
        except OSError:
            return False  # another thief won this generation
        payload["gen"] = gen
        try:
            write_json_atomic(path, payload)
        except OSError:
            return False
        return True

    def renew(self, jid: str, daemon_id: str) -> bool:
        """Heartbeat-renew a held lease.  Returns False when the lease
        is gone or was stolen — the caller has LOST the job and must
        not publish its result."""
        cur = self._read_lease(jid)
        if cur is None or cur["daemon"] != daemon_id:
            return False
        try:
            os.utime(self.lease_path(jid))
        except OSError:
            return False
        return True

    def owns(self, jid: str, daemon_id: str) -> bool:
        cur = self._read_lease(jid)
        return cur is not None and cur["daemon"] == daemon_id

    def release(self, jid: str, daemon_id: str) -> None:
        """Drop a held lease (job finished or requeued).  Steal latches
        for past generations are cleaned up with it."""
        if not self.owns(jid, daemon_id):
            return
        path = self.lease_path(jid)
        prefix = os.path.basename(path) + ".steal."
        try:
            os.unlink(path)
        except OSError:
            pass
        try:
            for name in os.listdir(self.leases_dir):
                if name.startswith(prefix):
                    os.unlink(os.path.join(self.leases_dir, name))
        except OSError:
            pass

    # ---- cross-daemon retry budget -------------------------------------
    def spend_retry(self, jid: str, budget: int) -> Optional[int]:
        """Spend one unit of the job's fleet-wide retry budget (an
        O_EXCL latch per unit, the faults.py `_claim` pattern — shared
        by every daemon on the spool, unlike a per-process counter).
        Returns the attempt number (1-based) or None when exhausted."""
        for i in range(max(0, int(budget))):
            latch = os.path.join(self.retries_dir, f"{jid}.r{i}")
            try:
                os.close(os.open(latch,
                                 os.O_CREAT | os.O_EXCL | os.O_WRONLY,
                                 0o644))
                return i + 1
            except OSError:
                continue
        return None

    def retries_spent(self, jid: str) -> int:
        try:
            return sum(1 for n in os.listdir(self.retries_dir)
                       if n.startswith(f"{jid}.r"))
        except OSError:
            return 0

    # ---- poison-job quarantine -----------------------------------------
    def quarantine_path(self, jid: str) -> str:
        return os.path.join(self.quarantine_dir, f"{jid}.json")

    def quarantine(self, jid: str, verdict: str,
                   context: Optional[Dict[str, Any]] = None,
                   trace_tail_lines: int = 40) -> Dict[str, Any]:
        """Park a poison job: capture its record, the fault context,
        and the tail of its per-job trace, then retire it from the
        live queue so no daemon picks it up again."""
        job = self.load(jid) or {"id": jid}
        rec = dict(job)
        rec["status"] = "quarantined"
        rec["quarantined_at"] = time.time()
        rec["verdict"] = verdict
        rec["retries_spent"] = self.retries_spent(jid)
        if context:
            rec["fault_context"] = context
        tail = []
        try:
            with open(self.trace_path(jid), encoding="utf-8") as fh:
                tail = fh.readlines()[-trace_tail_lines:]
        except OSError:
            pass
        if tail:
            rec["trace_tail"] = [ln.rstrip("\n") for ln in tail]
        self._write_hard(self.quarantine_path(jid), rec)
        try:
            os.unlink(self.job_path(jid))
        except OSError:
            pass
        try:
            os.unlink(self.lease_path(jid))
        except OSError:
            pass
        if self.tel is not None:
            self.tel.counter("serve.quarantined")
            self.tel.event("serve.quarantined", id=jid,
                           verdict=verdict)
        return rec

    def load_quarantined(self, jid: str) -> Optional[Dict[str, Any]]:
        try:
            with open(self.quarantine_path(jid),
                      encoding="utf-8") as fh:
                return json.load(fh)
        except (OSError, ValueError):
            return None

    def quarantined(self) -> List[Dict[str, Any]]:
        out = []
        try:
            names = sorted(os.listdir(self.quarantine_dir))
        except OSError:
            return out
        for name in names:
            if name.endswith(".json"):
                rec = self.load_quarantined(name[:-len(".json")])
                if rec is not None:
                    out.append(rec)
        return out

    # ---- takeover ------------------------------------------------------
    def takeover(self, jid: str, daemon_id: str, ttl: float,
                 retries: int) -> Optional[str]:
        """Steal a dead peer's in-flight job.  Only proceeds when the
        job is `running` and its lease is missing or expired; the lease
        steal latch guarantees a single winner, which then spends one
        cross-daemon retry and requeues — or quarantines the job when
        the budget is gone.  Returns "requeued", "quarantined", or
        None (lost the race / lease still live)."""
        job = self.load(jid)
        if job is None or job.get("status") != "running":
            return None
        cur = self._read_lease(jid)
        if cur is not None and cur["age"] <= ttl:
            return None  # the owner is still renewing
        if not self.try_claim(jid, daemon_id, ttl):
            return None
        attempt = self.spend_retry(jid, retries)
        if attempt is None:
            self.quarantine(
                jid,
                f"poison job: owner died {retries} times across the "
                f"fleet (cross-daemon retry budget exhausted)",
                context={"last_daemon": (cur or {}).get("daemon"),
                         "last_error": job.get("error"),
                         "requeue_note": job.get("requeue_note")})
            return "quarantined"
        self.mark(jid, "queued",
                  requeue_note=f"stolen after lease expiry "
                               f"(attempt {attempt}/{retries})",
                  stolen_by=daemon_id)
        self.release(jid, daemon_id)
        return "requeued"

    # ---- restart recovery ---------------------------------------------
    def recover(self, daemon_id: str = "recover",
                ttl: float = 0.0, retries: int = 0) -> int:
        """Re-queue jobs a previous daemon life left in flight:
        `drained` jobs (it checkpointed and parked them on SIGTERM)
        unconditionally; `running` jobs only when their lease is
        missing or expired — a job still leased by a LIVE peer on the
        same spool belongs to that peer.  Requeues of running jobs
        spend the cross-daemon retry budget when one is configured
        (retries > 0) and quarantine on exhaustion.  Returns the
        number re-queued."""
        n = 0
        for job in self.list_jobs():
            status = job.get("status")
            if status == "drained":
                self.mark(job["id"], "queued",
                          requeue_note="requeued after drain")
                n += 1
            elif status == "running":
                if retries > 0:
                    if self.takeover(job["id"], daemon_id, ttl,
                                     retries) == "requeued":
                        n += 1
                else:
                    cur = self._read_lease(job["id"])
                    if cur is not None and cur["age"] <= ttl:
                        continue  # a live peer owns it
                    self.mark(job["id"], "queued",
                              requeue_note="requeued after daemon "
                                           "restart")
                    n += 1
        return n

    # ---- the live-daemon stamp ----------------------------------------
    def stamp(self, **info) -> None:
        write_json_atomic(os.path.join(self.root, "serve.json"),
                          dict(info, stamped_at=time.time()))

    def read_stamp(self) -> Optional[Dict[str, Any]]:
        try:
            with open(os.path.join(self.root, "serve.json"),
                      encoding="utf-8") as fh:
                return json.load(fh)
        except (OSError, ValueError):
            return None
