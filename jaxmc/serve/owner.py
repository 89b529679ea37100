r"""The device-owner worker process (ISSUE 13).

The daemon's workers are THREADS: good for overlapping many jobs'
host-side work, but (a) CPU-bound interp jobs contend with the HTTP
loop for the GIL, and (b) one wedged XLA dispatch would stall every
thread behind the device.  By default (JAXMC_SERVE_DEVICE_OWNER=0
opts out) the daemon routes DEVICE work — cross-model vmapped batches
and solo device-backend jobs — to one spawned child process that owns
the accelerator.  A chip belongs to ONE process at a time, so this is
also what lets the daemon serve a real chip at all: run ONE DAEMON PER
CHIP (two daemons on a host each spawn an owner that contends for the
same chip; with the owner off the daemon's own threads initialize jax
and the daemon IS the chip's process — there is no placement layer):

  - the daemon process never initializes jax: HTTP + interp jobs keep
    the GIL to themselves;
  - a wedged or crashed dispatch kills (at worst) the owner process;
    the daemon detects the death, REQUEUES the in-flight jobs (their
    spool records simply go back to `queued` — no result was written,
    so nothing is lost) and respawns the owner lazily on the next
    device job;
  - SIGTERM-drain forwards to the child, whose engines park at their
    next safe boundary exactly like in-process engines do.

The owner speaks a tiny pickled request/response protocol over a
multiprocessing Pipe (spawn context — never fork a jax-initialized
daemon).  `run_vbatch` is the one batch runner, used by the owner child
AND by the daemon in-process when the owner is disabled, so the two
paths cannot drift.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Dict, List, Optional

from .. import obs


def _member_summary(res, jt, backend: str, spec: str,
                    serve_block: Dict[str, Any],
                    finished_on: Optional[str] = None) -> Dict[str, Any]:
    """ONE result-summary builder for every owner-run job (vbatch
    member or solo): the jaxmc.metrics result block, the rendered
    violation trace, and the serve block — shared so the two paths
    cannot drift.  Closes `jt`."""
    drained = bool(getattr(res, "drained", False))
    result_block: Dict[str, Any] = {
        "ok": res.ok, "distinct": res.distinct,
        "generated": res.generated, "diameter": res.diameter,
        "truncated": bool(res.truncated),
        "wall_s": round(res.wall_s, 6),
        "finished_on": finished_on or backend,
        "warnings": list(getattr(res, "warnings", []))}
    if drained:
        result_block["drained"] = True
    if res.violation is not None:
        from ..engine.explore import format_trace
        result_block["violation"] = {"kind": res.violation.kind,
                                     "name": res.violation.name}
        result_block["trace"] = format_trace(res.violation)
    summary = jt.summary(result=result_block)
    summary["backend"] = backend
    summary["spec"] = spec
    summary["serve"] = dict(
        serve_block,
        window_recompiles=sum(1 for lv in jt.levels
                              if lv.get("fresh_compile")),
        profile_hits=jt.counters.get("profile.hits", 0),
        persistent_cache_hits=jt.counters.get(
            "compile.persistent_cache_hits", 0),
        program_hits=jt.counters.get("compile.program_hits", 0))
    jt.close()
    return {"summary": summary, "ok": res.ok, "distinct": res.distinct,
            "generated": res.generated, "drained": drained}


def run_vbatch(members_desc: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Run one cross-model vmapped batch.  `members_desc` is one entry
    per DISTINCT job signature: {spec, cfg, options, jids: [job ids]}.
    Returns {"members": [...]} with per-member result/summary dicts, or
    {"incompatible": reason} when the cohort cannot share a program
    (the caller falls back to solo runs)."""
    from ..backend.batch import BatchCheckEngine, BatchIncompatible
    from .protocol import build_config
    t0 = time.time()
    cfgs, tels, envelope = [], [], None
    for md in members_desc:
        cfg = build_config(md["spec"], md.get("cfg"), md.get("options"))
        if md.get("checkpoint"):
            # batch-scoped per-member checkpoints (ISSUE 19): a drained
            # or stolen cohort re-forms and resumes each member from
            # its own bsig-scoped checkpoint; the batch engine clears
            # any resume whose lane plan no longer matches (fresh run,
            # never a refused job)
            cfg.checkpoint = md["checkpoint"]
            cfg.checkpoint_every = float(
                md.get("checkpoint_every", 60.0))
            cfg.final_checkpoint = True
            if os.path.exists(md["checkpoint"]):
                cfg.resume = md["checkpoint"]
        cfgs.append(cfg)
        tels.append(obs.Telemetry(trace_path=md.get("trace"), meta={
            "command": "serve.job", "job": md["jids"][0],
            "sig": md.get("sig"), "bsig": md.get("bsig"),
            "backend": cfg.backend, "spec": md["spec"],
            "cfg": md.get("cfg"), "env": obs.environment_meta()}))
        if envelope is None:
            # the cohort's envelope, on the leader's recorder as soon as
            # it exists: in the owner's device trace every idle piece is
            # then under a job's envelope or under none (the owner
            # waiting for the daemon)
            envelope = tels[0].span(
                "vbatch", members=len(members_desc)).__enter__()
    try:
        # the LEADER's recorder is the cohort's: the one build, the
        # supersteps' spans and the vmapped program's record reach the
        # client in the leader's artifact (the members' own recorders
        # keep their load, search, store and checkpoint)
        be = BatchCheckEngine(
            cfgs, tels=tels, tags=[md["jids"][0] for md in members_desc],
            tel=tels[0]).build()
    except BatchIncompatible as ex:
        envelope.done(error="BatchIncompatible")
        for jt in tels:
            jt.close()
        return {"incompatible": str(ex)}
    members = be.run()
    disp = be.dispatcher
    envelope.done()
    t1 = time.time()
    wall = t1 - t0
    out: List[Dict[str, Any]] = []
    for md, cfg, mem, jt in zip(members_desc, cfgs, members, tels):
        if mem.error is not None:
            jt.close()
            out.append({"error":
                        f"{type(mem.error).__name__}: {mem.error}"})
            continue
        res = mem.result
        if not res.ok and res.violation is not None and \
                res.violation.kind == "error":
            # an engine-level abort (OV_PACK profile gap, capacity
            # overflow) is NOT this job's verdict: a SOLO run recovers
            # via adaptive relayout, which the shared batch program
            # cannot do — hand the member back for a solo retry
            jt.close()
            why = res.violation.message or res.violation.name
            out.append({"retry_solo":
                        f"batch member aborted ({why}); solo relayout "
                        f"recovery applies"})
            continue
        out.append(_member_summary(mem.result, jt, cfg.backend,
                                   md["spec"], {
            "sig": md.get("sig"), "bsig": md.get("bsig"),
            "warm_engine": False,
            "resumed_from_checkpoint": bool(
                getattr(mem, "resumed", False)),
            "batched_with": [j for m2 in members_desc
                             for j in m2["jids"]
                             if j not in md["jids"]],
            "batch_occupancy": disp.max_width,
            "batch_dispatches": disp.dispatches,
            "lifted_consts": list(be.lift_names),
            "job_wall_s": round(wall, 6),
            # the owner's two stations (serve/protocol.py): every
            # member carries the cohort's
            "stations": {"owner_began_at": t0, "owner_ended_at": t1},
        }))
    return {"members": out, "occupancy": disp.max_width,
            "dispatches": disp.dispatches,
            "lift": list(be.lift_names),
            "engine_builds": be.engine_builds,
            "build_wall_s": round(be.build_wall_s, 6),
            "wall_s": round(wall, 6)}


# sig -> {"session": CheckSession, "completed": bool} — the OWNER'S
# warm registry (ISSUE 19): with the owner process on by default, the
# already-compiled engine must live WHERE THE DEVICE IS.  The same
# bounded-LRU discipline as the daemon's in-process registry
# (JAXMC_SERVE_WARM_MAX), the same checkpoint-replay reuse gate.  The
# owner serves one request at a time (the daemon serializes on the
# pipe), so no locking is needed here.
_WARM: "collections.OrderedDict[str, Dict[str, Any]]" = \
    collections.OrderedDict()


def _warm_max() -> int:
    try:
        return max(1, int(os.environ.get(
            "JAXMC_SERVE_WARM_MAX", "32") or 32))
    except ValueError:
        return 32


def _revalidate_profile(sess, job_tel) -> None:
    """Confirm the durable capacity profile still matches the warm
    engine's layout (counts as a profile hit in the job's artifact) —
    the daemon-side warm path's check, mirrored for the owner."""
    if sess.layout_sig and sess.model is not None:
        from ..compile.cache import load_capacity_profile
        desc = getattr(sess.engine, "backend_desc", None)
        variant = desc.profile_variant() if desc is not None else ""
        load_capacity_profile(sess.model.module.name,
                              sess.layout_sig, tel=job_tel,
                              variant=variant)


def run_solo(md: Dict[str, Any]) -> Dict[str, Any]:
    """Run one solo device job in the owner process: the same
    CheckSession flow the daemon's _run_batch drives, including a warm
    registry of its own — a repeat signature replays the finalized
    checkpoint on the already-compiled engine with zero in-window
    recompiles.  Returns {"summary", "ok", ...} or {"error"}."""
    from ..session import CheckSession
    from .protocol import build_config
    t0 = time.time()
    cfg = build_config(md["spec"], md.get("cfg"), md.get("options"))
    ck = md.get("checkpoint")
    if ck:
        cfg.checkpoint = ck
        cfg.checkpoint_every = float(md.get("checkpoint_every", 60.0))
        cfg.final_checkpoint = True
        if os.path.exists(ck):
            cfg.resume = ck
    jt = obs.Telemetry(trace_path=md.get("trace"), meta={
        "command": "serve.job", "job": md["jids"][0],
        "sig": md.get("sig"), "backend": cfg.backend,
        "spec": md["spec"], "cfg": md.get("cfg"),
        "env": obs.environment_meta()})
    # the job's envelope on its own recorder (see run_vbatch)
    envelope = jt.span("job").__enter__()
    sig = md.get("sig")
    entry = _WARM.get(sig) if sig else None
    # the warm/replay decision (a completed entry AND its finalized
    # checkpoint on disk): THIS is the reference since the owner is the
    # default device path; daemon._run_batch_inner mirrors it for
    # in-process jobs
    warm_engine = bool(entry is not None and entry.get("completed")
                       and ck and os.path.exists(ck))
    resumed = bool(cfg.resume)
    # per-JOB watchdog (ISSUE 16): the stall threshold derives from
    # this job's own level rhythm, never a neighbour's
    wd = obs.Watchdog(jt).start()
    try:
        with obs.use_local(jt):
            if warm_engine:
                # WARM: replay the finalized checkpoint on the
                # already-compiled engine; rebind its telemetry to
                # THIS job's recorder first (the cold job's closed)
                resumed = True
                _WARM.move_to_end(sig)
                sess = entry["session"]
                sess.tel = jt
                sess.log = obs.Logger(jt, quiet=True)
                _revalidate_profile(sess, jt)
                res = sess.explore(resume_from=ck, checkpoint_path=ck,
                                   final_checkpoint=True)
            else:
                sess = CheckSession(cfg, tel=jt,
                                    log=obs.Logger(jt, quiet=True))
                sess.parse()
                try:
                    sess.compile()
                    res = sess.explore()
                except (RuntimeError, OSError, MemoryError,
                        ConnectionError) as ex:
                    res = sess.demote_to_cpu(ex)
                if sig:
                    drained = bool(getattr(res, "drained", False))
                    _WARM[sig] = {"session": sess,
                                  "completed": res.ok and
                                  not res.truncated and not drained}
                    _WARM.move_to_end(sig)
                    while len(_WARM) > _warm_max():
                        _WARM.popitem(last=False)
    except Exception as ex:  # noqa: BLE001 — the job's failure is its
        # verdict; the owner loop must survive to serve the next one
        envelope.done(error=type(ex).__name__)
        jt.close()
        return {"error": f"{type(ex).__name__}: {ex}"}
    finally:
        wd.stop()
    envelope.done()
    t1 = time.time()
    return _member_summary(res, jt, cfg.backend, md["spec"], {
        "sig": sig, "warm_engine": warm_engine,
        "resumed_from_checkpoint": resumed,
        "device_owner": True,
        "batched_with": [],
        "job_wall_s": round(t1 - t0, 6),
        "stations": {"owner_began_at": t0, "owner_ended_at": t1},
    }, finished_on=sess.finished_on)


def _owner_main(conn) -> None:
    """The owner child's request loop (spawn target — keep this
    module-level and import-light)."""
    import signal
    from .. import drain
    drain.clear()
    signal.signal(signal.SIGTERM,
                  lambda *_: drain.request("device-owner SIGTERM"))
    while True:
        try:
            req = conn.recv()
        except (EOFError, OSError):
            return
        kind = req.get("kind")
        if kind == "stop":
            conn.send({"stopped": True})
            return
        if kind == "ping":
            conn.send({"pong": True, "pid": os.getpid()})
            continue
        try:
            if kind == "vbatch":
                resp = run_vbatch(req["members"])
            elif kind == "solo":
                resp = run_solo(req["member"])
            else:
                resp = {"error": f"unknown request kind {kind!r}"}
        except BaseException as ex:  # noqa: BLE001 — report, don't die
            resp = {"error": f"{type(ex).__name__}: {ex}"}
        try:
            conn.send(resp)
        except (BrokenPipeError, OSError):
            return


class OwnerDied(Exception):
    """The owner process died (or timed out) with a request in flight.
    `timed_out` distinguishes a POLICY kill (the request exceeded
    JAXMC_SERVE_OWNER_TIMEOUT — requeueing would livelock: the re-run
    hits the same deadline) from a genuine death (requeue + respawn is
    the right recovery)."""

    def __init__(self, msg: str, timed_out: bool = False):
        super().__init__(msg)
        self.timed_out = timed_out


class DeviceOwner:
    """Parent-side handle: lazy spawn, serialized requests, death
    detection, respawn accounting."""

    def __init__(self, log=None, timeout: Optional[float] = None):
        import multiprocessing as mp
        self._mp = mp.get_context("spawn")
        self._proc = None
        self._conn = None
        self._lock = threading.Lock()
        self.log = log or (lambda *_: None)
        self.timeout = timeout if timeout is not None else float(
            os.environ.get("JAXMC_SERVE_OWNER_TIMEOUT", "3600"))
        self.spawns = 0

    @property
    def pid(self) -> Optional[int]:
        return self._proc.pid if self._proc is not None else None

    def alive(self) -> bool:
        return self._proc is not None and self._proc.is_alive()

    def _spawn_locked(self) -> None:
        parent, child = self._mp.Pipe()
        self._proc = self._mp.Process(target=_owner_main, args=(child,),
                                      name="jaxmc-device-owner",
                                      daemon=True)
        # the spawn context snapshots os.environ at start(): export the
        # trace header for that window so the owner (and every job it
        # runs) joins the daemon's trace — a respawned owner re-reads
        # the SAME header, keeping the original trace_id
        with obs.context.exported():
            self._proc.start()
        child.close()
        self._conn = parent
        self.spawns += 1
        self.log(f"serve: device-owner process spawned "
                 f"(pid {self._proc.pid})")

    def request(self, req: Dict[str, Any],
                timeout: Optional[float] = None,
                stations: Optional[Dict[str, float]] = None,
                tel=None) -> Dict[str, Any]:
        """Send one request; block for the response.  Raises OwnerDied
        if the child dies or the deadline passes — the owner is then
        torn down so the next request respawns a fresh one.

        THIS call's stations (serve/protocol.py) go into `stations`,
        never onto the shared handle (two workers call this):
        `owner_spawned_at` where the call had to spawn the owner,
        `owner_sent_at`, `owner_received_at`.  On `tel` the same two
        borders cut the caller's open span into `job.owner_wait` (the
        lock wanted -> the lock held and an owner alive: another
        worker's whole job, a spawn) and `job.owner_run` (the request
        into the pipe -> its answer out of it)."""
        st = stations if stations is not None else {}
        tel = tel if tel is not None else obs.NullTelemetry()
        span = tel.span("job.owner_wait").__enter__()
        try:
            with self._lock:
                # the deadline starts when THIS request is actually
                # sent: time spent waiting behind another worker's long
                # job must not count against it (a healthy owner would
                # be killed)
                deadline = time.time() + (timeout if timeout is not None
                                          else self.timeout)
                if not self.alive():
                    self._spawn_locked()
                    st["owner_spawned_at"] = time.time()
                # stamped as the request ENTERS the pipe: the owner can
                # begin before the line after `send` runs (0.17 ms seen)
                st["owner_sent_at"] = time.time()
                span.done()
                span = tel.span("job.owner_run").__enter__()
                try:
                    self._conn.send(req)
                except (BrokenPipeError, OSError):
                    # a broken pipe makes the child unusable even if it
                    # is still alive: kill it so the next request
                    # respawns
                    self._kill_locked()
                    raise OwnerDied("owner pipe closed on send")
                while True:
                    try:
                        if self._conn.poll(0.2):
                            resp = self._conn.recv()
                            st["owner_received_at"] = time.time()
                            return resp
                    except (EOFError, OSError):
                        self._kill_locked()
                        raise OwnerDied("owner pipe closed mid-request")
                    if not self._proc.is_alive():
                        self._reap_locked()
                        raise OwnerDied(
                            f"owner process died (exitcode "
                            f"{self._proc.exitcode if self._proc else '?'})")
                    if time.time() > deadline:
                        self._kill_locked()
                        raise OwnerDied(
                            "owner request exceeded "
                            "JAXMC_SERVE_OWNER_TIMEOUT "
                            f"({self.timeout:.0f}s); raise it for "
                            "longer-running cohorts", timed_out=True)
        except OwnerDied:
            span.done(error="OwnerDied")
            raise
        finally:
            span.done()

    def _reap_locked(self) -> None:
        if self._conn is not None:
            try:
                self._conn.close()
            except OSError:
                pass
        self._conn = None

    def _kill_locked(self) -> None:
        self._reap_locked()
        if self._proc is not None and self._proc.is_alive():
            self._proc.kill()
            self._proc.join(timeout=5.0)
        self._proc = None

    def drain(self) -> None:
        """Forward the daemon's drain: SIGTERM the child so its engines
        park at their next safe boundary."""
        if self.alive():
            self._proc.terminate()

    def stop(self, timeout: float = 30.0) -> None:
        with self._lock:
            if not self.alive():
                self._kill_locked()
                return
            try:
                self._conn.send({"kind": "stop"})
                t0 = time.time()
                while self._proc.is_alive() and \
                        time.time() - t0 < timeout:
                    time.sleep(0.05)
            except (BrokenPipeError, OSError):
                pass
            self._kill_locked()
