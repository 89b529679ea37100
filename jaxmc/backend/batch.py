r"""Cross-model vmapped batching (ISSUE 13): one dispatch serves many
layout-compatible jobs.

The model-checking analogue of continuous batching in LLM serving
(Orca, OSDI '22).  The serve fleet's old batching coalesced IDENTICAL
jobs only; here B *different but layout-compatible* models share one
compiled device program:

  compat   two models are batch-compatible when they differ only in
           LIFTABLE constant values (analyze/bounds.liftable_constants:
           ints used purely in value positions) — everything that shapes
           the layout, the arm structure, or the dedup key basis is
           equal.  session.batch_signature proves this at PARSE time,
           before any engine exists.
  compile  ONE donor engine builds the layout (lane plan over the union
           of every member's sampled states; proven bounds interval-
           merged across members) and the kernels, with the lifted
           constants as traced inputs (kernel2 const_lanes).  Followers
           clone the donor (TpuExplorer(donor=...)): zero sampling,
           zero kernel builds.
  dispatch every member runs the UNCHANGED host_seen BFS loop — its own
           init states, native fingerprint store, trace bookkeeping,
           verdicts — but its per-chunk device call routes through the
           shared BatchDispatcher, which waits until every ACTIVE
           member has a pending chunk and then runs ONE
           jit(vmap(hstep_core)) over [B, CH, PW] frontiers + [B]
           counts + [B, n_lift] constant vectors.  The same program
           lays each member's nine outputs into ONE int32 block
           (`_pack_lane`), the dispatcher fetches the live members'
           blocks in ONE device-to-host transfer, and a member reads
           the nine names back as numpy views into its block
           (`_unpack_lane`): what a member sees is what the solo step
           returns, as host arrays.
  ragged   per-member frontier occupancy is handled by the step's own
           validity masks (fcount per lane); a member that finishes —
           exhaustion, violation, truncation, drain — DEREGISTERS and
           its lane goes idle-masked: membership changes between
           supersteps without recompiling (the continuous-batching
           move).

Because each member's host loop IS the solo engine's loop and
vmap(f)(stack(xs))[i] == f(xs[i]) exactly over integer kernels, per-job
counts, traces, and verdicts are byte-identical to solo runs — batching
is a throughput optimization, never a semantics change.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .. import obs
from ..compile.vspec import Bounds, CompileError, ModeError
from ..engine.simulate import sample_states
from ..sem.enumerate import enumerate_init
from .bfs import KEY_FN, SENTINEL, TpuExplorer, _pow2_at_least


class BatchIncompatible(Exception):
    """The cohort cannot share one program; the message names why.  The
    caller (the serve daemon) falls back to solo runs."""


@dataclass
class _MergedBounds:
    """Shim BoundsReport for the donor build: the interval-UNION of
    every member's converged proof, sound for all of them."""
    merged: Dict[str, Tuple[int, int]]
    merged_eb: Dict[str, Any] = field(default_factory=dict)
    converged: bool = True

    def lane_bounds(self) -> Dict[str, Tuple[int, int]]:
        return self.merged

    def element_bounds(self) -> Dict[str, Any]:
        # structural merge (ISSUE 18): per-element trees where every
        # member proved one, backed by the lane interval for variables
        # whose structured merge collapsed — the donor plan never packs
        # wider than the worst solo member would
        from ..analyze.bounds import EB
        out: Dict[str, Any] = dict(self.merged_eb)
        for v, iv in self.merged.items():
            if v not in out:
                out[v] = EB(all=iv)
        return out


# ---- the superstep's result block -------------------------------------
# What a superstep brings back is ONE int32 block a member, words-major:
# [PW + K + 1, C + _HDR], the C = A * CH candidate slots in the minor
# dimension.  Rows 0..PW-1 are the packed candidate words (`cand`
# transposed), the next K the key lanes (`keys` transposed), the last
# row a flags word a slot; the _HDR columns past the slots are the
# header: `gen` and `overflow` in the flags row, zeros above.  Where C
# is a multiple of 128 (any chunk of 128 rows or more) the chip's
# default layout for the block is the (8, 128) tiling it computes in,
# with no temporaries; a slots-major [C, PW + K + 1] block is laid
# words-major on the device all the same, its transpose left to the
# transfer, and fetches no faster (PERF.md section 6, PR 40).
_HDR = 128
_F_CVALID, _F_INV_OK, _F_EXPLORE, _F_ASSERT, _F_DEAD = 1, 2, 4, 8, 16


def _pack_lane(out: Dict[str, Any]):
    """hstep_core's nine outputs of ONE member as its int32 block (on
    the device, inside the vmapped program)."""
    A, CH = out["assert_bad"].shape
    C = A * CH

    def bits(x, flag):
        return jnp.where(x, jnp.int32(flag), jnp.int32(0))

    flags = bits(out["cvalid"], _F_CVALID) \
        | bits(out["inv_ok"], _F_INV_OK) \
        | bits(out["explore"], _F_EXPLORE) \
        | bits(out["assert_bad"].reshape(C), _F_ASSERT) \
        | jnp.pad(bits(out["dead"], _F_DEAD), (0, C - CH))
    body = jnp.concatenate(
        [out["cand"].T, out["keys"].T, flags[None]], axis=0)
    head = jnp.zeros((body.shape[0], _HDR), jnp.int32) \
        .at[-1, 0].set(out["gen"]).at[-1, 1].set(out["overflow"])
    return jnp.concatenate([body, head], axis=1)


def _packed(core):
    """vmap of `core` with every member's outputs laid into its block:
    (frontiers [B, CH, PW], fcounts [B], cvecs [B, n_lift]) ->
    int32 [B, PW + K + 1, C + _HDR]."""
    def packed_core(frontier_p, fcount, cvec):
        return _pack_lane(core(frontier_p, fcount, cvec))

    return jax.vmap(packed_core)


def _unpack_lane(blk: np.ndarray, PW: int, CH: int) -> Dict[str, Any]:
    """One member's block back as hstep_core's nine names: numpy VIEWS
    into `blk` for the words (`cand` [C, PW], `keys` [C, K]: transposed
    views, so a row take `keys[idx]` gathers columns of the block and
    nothing transposes the whole of it), bools decoded from the flags
    row, `gen` / `overflow` the header's int32 scalars."""
    C = blk.shape[1] - _HDR
    flags = blk[-1, :C]
    return dict(
        cand=blk[:PW, :C].T, keys=blk[PW:-1, :C].T,
        cvalid=(flags & _F_CVALID) != 0,
        inv_ok=(flags & _F_INV_OK) != 0,
        explore=(flags & _F_EXPLORE) != 0,
        assert_bad=((flags & _F_ASSERT) != 0).reshape(C // CH, CH),
        dead=(flags[:CH] & _F_DEAD) != 0,
        gen=blk[-1, C], overflow=blk[-1, C + 1])


def _live_lanes(blk, idx):
    return blk.at[idx].get(mode="promise_in_bounds",
                           indices_are_sorted=True, unique_indices=True)


# the live lanes of a block, gathered on the device before the fetch:
# one small program per WIDTH (the index's length), shared by every
# cohort of the process
_take_lanes = obs.prof_wrap("batch.take", jax.jit(_live_lanes))


class BatchDispatcher:
    """The superstep barrier: collects one pending device chunk per
    ACTIVE member, runs ONE vmapped dispatch, hands each member its
    slice.  The thread that completes the barrier executes the dispatch
    inline (every other member is blocked waiting on its slice)."""

    def __init__(self, donor: TpuExplorer, cvecs: np.ndarray,
                 tel=None):
        self.CH = _pow2_at_least(donor.chunk, lo=64)
        self.B = len(cvecs)
        self.PW = donor.PW
        self._core = donor._hstep_core(self.CH)
        self._vstep = obs.prof_wrap("batch.vstep",
                                    jax.jit(_packed(self._core)))
        self._cvecs = jnp.asarray(np.ascontiguousarray(cvecs, np.int32))
        # the cohort's recorder: every superstep's spans, the site's
        # launch seconds and the vmapped program's record land here,
        # whichever member's thread completes the barrier
        self.tel = tel if tel is not None else obs.NullTelemetry()
        self._cv = threading.Condition()
        self._active: set = set(range(self.B))
        self._pending: Dict[int, Tuple[np.ndarray, int]] = {}
        self._results: Dict[int, Any] = {}
        self._lane_idx: Dict[Tuple[int, ...], Any] = {}
        self._gen = 0            # dispatch generation (wakeup marker)
        self.dispatches = 0
        self.max_width = 0
        self.widths: List[int] = []

    def reset(self) -> None:
        """Re-arm for another cohort run (bench warm re-runs): all
        lanes active again, superstep state and PER-RUN STATS cleared
        (the artifact's dispatch count must describe one run, not the
        lifetime).  The compiled vmapped program is untouched — that is
        the warm artifact."""
        with self._cv:
            self._active = set(range(self.B))
            self._pending.clear()
            self._results.clear()
            self.dispatches = 0
            self.max_width = 0
            self.widths = []

    # ---- member surface ------------------------------------------------
    def hstep_factory(self, slot: int):
        """The _hstep_override for member `slot`: returns a callable
        with the solo hstep's signature whose device work goes through
        the shared vmapped program."""
        def factory(CH: int):
            if CH != self.CH:
                raise ModeError(
                    f"batch member chunk capacity {CH} != shared "
                    f"dispatcher capacity {self.CH}")

            def hstep(frontier_p, fcount):
                return self._step(slot, frontier_p, int(fcount))

            return hstep

        return factory

    def deregister(self, slot: int) -> None:
        """Membership change between supersteps: the member is done (or
        failed); remaining members' barrier no longer waits for it."""
        with self._cv:
            self._active.discard(slot)
            self._pending.pop(slot, None)
            if self._active and \
                    set(self._pending) >= self._active:
                self._fire_locked()
            self._cv.notify_all()

    # ---- the superstep -------------------------------------------------
    def _step(self, slot: int, frontier_p, fcount: int
              ) -> Dict[str, Any]:
        t0 = time.perf_counter()
        fired = 0.0
        with self._cv:
            self._pending[slot] = (np.asarray(frontier_p, np.int32),
                                   fcount)
            if set(self._pending) >= self._active:
                t1 = time.perf_counter()
                self._fire_locked()
                fired = time.perf_counter() - t1
            while slot not in self._results:
                self._cv.wait(0.5)
            res = self._results.pop(slot)
        # the member's own recorder (the member thread's current one):
        # its seconds in this step that were not the firing itself —
        # the wait for the lock, for the slower members' chunks and for
        # the dispatch another thread ran
        obs.current().counter("batch.barrier_wait_s",
                              time.perf_counter() - t0 - fired)
        if isinstance(res, BaseException):
            # the shared dispatch failed: EVERY waiter gets the
            # error (not just the thread that fired) — each member
            # fails its own run and deregisters, so the cohort
            # never deadlocks on a lane that cannot re-fire
            raise RuntimeError(
                f"vmapped batch dispatch failed: "
                f"{type(res).__name__}: {res}") from res
        return _unpack_lane(res, self.PW, self.CH)

    def _lanes(self, slots: Tuple[int, ...]):
        """The device index of the live lanes `slots`, made once a
        membership: members leave one by one, so a run sees few."""
        idx = self._lane_idx.get(slots)
        if idx is None:
            idx = self._lane_idx[slots] = jnp.asarray(
                np.asarray(slots, np.int32))
        return idx

    def _fire_locked(self) -> None:
        """One vmapped dispatch over every pending member lane (caller
        holds the condition).  A dispatch failure is distributed to
        every pending slot as its result — see _step.

        What comes back is the program's packed result, one int32
        block a member, in ONE transfer: all B blocks where every lane
        is pending, else the pending lanes' alone, gathered on the
        device first (`_take_lanes`: an idle lane's block is sentinel
        padding nobody reads).  A member gets its block as it arrived
        and decodes it on its own thread, outside the lock (`_step`).

        In the cohort's recorder, whichever member's thread fires: the
        span `batch.dispatch` (upload, the vmapped program, the block
        fetched: a synchronous round trip, and the span a trace's
        dispatches are counted by) and counters round it, which cost a
        dispatch no event — `batch.stack_s` (the host stacks the
        pending chunks into one [B, CH, PW] block), `batch.unstack_s`
        (each member handed its block), `batch.upload_s` and
        `batch.fetch_s` (the round trip's two ends, round the site's
        own launch seconds; the fetch holds the wait for the program
        and the live lanes' gather), `batch.fetch_transfers` (device-
        to-host transfers: one a dispatch) and `batch.fetch_mb` (bytes
        brought back / 10^6), and `batch.first_dispatch_s` (the
        cohort's first call alone: trace, lower, compile or load)."""
        rec = self.tel
        slots = sorted(self._pending)
        width = len(slots)
        with rec.timed("batch.stack_s"):
            fr = np.full((self.B, self.CH, self.PW), SENTINEL, np.int32)
            fc = np.zeros(self.B, np.int32)
            for s in slots:
                bf, c = self._pending[s]
                fr[s] = bf
                fc[s] = c
            self._pending.clear()
        try:
            with obs.use_local(rec), rec.span("batch.dispatch"):
                t0 = time.perf_counter()
                args = (jnp.asarray(fr), jnp.asarray(fc), self._cvecs)
                t1 = time.perf_counter()
                blk = self._vstep(*args)
                t2 = time.perf_counter()
                if width < self.B:
                    blk = _take_lanes(blk, self._lanes(tuple(slots)))
                blk = np.asarray(blk)
                rec.counter("batch.upload_s", t1 - t0)
                rec.counter("batch.fetch_s", time.perf_counter() - t2)
                rec.counter("batch.fetch_transfers")
                rec.counter("batch.fetch_mb", blk.nbytes / 1e6)
                if not self.dispatches:
                    rec.counter("batch.first_dispatch_s", t2 - t1)
        except Exception as ex:  # noqa: BLE001 — XLA runtime/OOM/
            # compile failures land on every waiting member
            for s in slots:
                self._results[s] = ex
            self._cv.notify_all()
            return
        with rec.timed("batch.unstack_s"):
            for i, s in enumerate(slots):
                self._results[s] = blk[i]
        self.dispatches += 1
        self.max_width = max(self.max_width, width)
        self.widths.append(width)
        rec.gauge("batch.width", width)
        rec.counter("batch.dispatches")
        rec.counter("batch.lane_steps", width)
        self._cv.notify_all()


@dataclass
class BatchMember:
    """One job in the cohort: its model, engine, telemetry channel and
    (after run) result or error."""
    model: Any
    engine: Optional[TpuExplorer] = None
    tel: Any = None
    result: Any = None
    error: Optional[BaseException] = None
    tag: Optional[str] = None  # caller's handle (job id)
    warnings: List[str] = field(default_factory=list)
    resumed: bool = False      # engine restored from its checkpoint


# engine-relevant option surface every member must share (per-model
# differences ride the lifted constant lanes, nothing else)
_SHARED_FIELDS = ("include", "no_deadlock", "max_states", "seq_cap",
                  "grow_cap", "kv_cap", "no_trace", "sample", "chunk")


class BatchCheckEngine:
    """B layout-compatible CheckSession configs -> one donor engine +
    B-1 follower clones -> one vmapped dispatch sequence -> B solo-
    identical CheckResults."""

    def __init__(self, cfgs: List[Any], tels: Optional[List[Any]] = None,
                 tags: Optional[List[str]] = None, log=None, tel=None):
        if len(cfgs) < 1:
            raise ValueError("empty batch")
        self.cfgs = cfgs
        self.tel = tel if tel is not None else obs.current()
        self.log = log if log is not None else obs.Logger(self.tel,
                                                          quiet=True)
        self.members: List[BatchMember] = []
        self.dispatcher: Optional[BatchDispatcher] = None
        self.lift_names: Tuple[str, ...] = ()
        self._tels = tels or [None] * len(cfgs)
        self._tags = tags or [None] * len(cfgs)
        self.build_wall_s = 0.0

    # ---- compat proof + build -----------------------------------------
    def build(self) -> "BatchCheckEngine":
        """Span `batch.build` in the cohort's recorder, which is also
        this thread's recorder while it lasts: the donor engine's own
        build spans and gauges (`layout_sample`, `compile_arm`, ...)
        land beside `load`, `batch_sample` and `engine_build`."""
        with obs.use_local(self.tel), \
                self.tel.span("batch.build", members=len(self.cfgs)):
            return self._build()

    def _build(self) -> "BatchCheckEngine":
        from ..analyze.bounds import (infer_state_bounds,
                                      liftable_constants,
                                      merge_element_bounds,
                                      merge_lane_bounds)
        from ..session import load_model
        t0 = time.time()
        c0 = self.cfgs[0]
        for c in self.cfgs[1:]:
            for f in _SHARED_FIELDS:
                if getattr(c, f) != getattr(c0, f):
                    raise BatchIncompatible(
                        f"member option {f!r} differs "
                        f"({getattr(c, f)!r} vs {getattr(c0, f)!r})")
        models = []
        for c, jt in zip(self.cfgs, self._tels):
            with (jt or self.tel).span("load", spec=c.spec):
                models.append(load_model(c.spec, c.cfg, c.no_deadlock,
                                         c.include))
        m0 = models[0]
        lift = liftable_constants(m0)
        for m in models[1:]:
            if m.module.name != m0.module.name:
                raise BatchIncompatible(
                    f"module {m.module.name!r} != {m0.module.name!r}")
            if tuple(m.vars) != tuple(m0.vars):
                raise BatchIncompatible("state variables differ")
            if liftable_constants(m) != lift:
                raise BatchIncompatible("liftable-constant sets differ")
            if set(m.cfg.constants) != set(m0.cfg.constants):
                raise BatchIncompatible("cfg CONSTANT names differ")
            for n in m.cfg.constants:
                if n not in lift and \
                        m.defs.get(n) != m0.defs.get(n):
                    raise BatchIncompatible(
                        f"non-liftable constant {n} differs "
                        f"({m.defs.get(n)!r} vs {m0.defs.get(n)!r}) — "
                        f"it shapes the layout, so the models are not "
                        f"layout-compatible")
        self.lift_names = lift
        self.members = [BatchMember(model=m, tel=t, tag=g)
                        for m, t, g in zip(models, self._tels,
                                           self._tags)]

        # ONE layout over the union of every member's sampled states,
        # with the proven bounds interval-merged so no member's values
        # can trip another's proof
        bfs_n, walks, depth = tuple(c0.sample)
        extra: List[Dict[str, Any]] = []
        reports = []
        # a follower's Init is walked once, here, and the list handed on
        # to its engine (the donor walks its own, ISSUE 52)
        follower_inits: List[List[Dict[str, Any]]] = []
        with self.tel.span("batch_sample", members=len(models)):
            for m in models:
                reports.append(infer_state_bounds(m))
                if m is not m0:
                    inits = enumerate_init(m.init, m.ctx(), m.vars)
                    follower_inits.append(inits)
                    extra.extend(sample_states(m, bfs_states=bfs_n,
                                               n_walks=walks,
                                               walk_depth=depth,
                                               inits=inits))
        merged = merge_lane_bounds(
            [r.lane_bounds() if r is not None and r.converged else None
             for r in reports])
        merged_eb = merge_element_bounds(
            [r.element_bounds() if r is not None and r.converged
             else None for r in reports])
        m0._bounds_report = _MergedBounds(merged=merged,
                                          merged_eb=merged_eb)

        bounds = Bounds(seq_cap=c0.seq_cap, grow_cap=c0.grow_cap,
                        kv_cap=c0.kv_cap)
        with self.tel.span("engine_build", batch=len(models)):
            try:
                donor = TpuExplorer(
                    m0, log=self.log, bounds=bounds,
                    store_trace=not c0.no_trace,
                    progress_every=c0.progress_every,
                    host_seen=True, chunk=c0.chunk,
                    sample_cfg=tuple(c0.sample),
                    extra_samples=extra,
                    max_states=c0.max_states,
                    relayouts_left=0,
                    checkpoint_path=c0.checkpoint,
                    checkpoint_every=c0.checkpoint_every,
                    resume_from=c0.resume,
                    final_checkpoint=c0.final_checkpoint,
                    lift_consts=lift)
            except (CompileError, ModeError) as ex:
                raise BatchIncompatible(
                    f"lifted-constant compile failed: {ex}")
        reason = donor.batch_block_reason()
        if reason is not None:
            raise BatchIncompatible(f"donor engine not batchable: "
                                    f"{reason}")
        self.members[0].engine = donor
        for mem, c, inits in zip(self.members[1:], self.cfgs[1:],
                                 follower_inits):
            mem.engine = TpuExplorer(
                mem.model, donor=donor, log=self.log,
                max_states=c0.max_states,
                store_trace=not c0.no_trace,
                progress_every=c0.progress_every,
                checkpoint_path=c.checkpoint,
                checkpoint_every=c.checkpoint_every,
                resume_from=c.resume,
                final_checkpoint=c.final_checkpoint,
                inits=inits)
        self._validate_resumes()
        cvecs = np.stack([mem.engine._cvec for mem in self.members]) \
            if lift else np.zeros((len(self.members), 0), np.int32)
        self.dispatcher = BatchDispatcher(donor, cvecs, tel=self.tel)
        # MEASURED engine-build count for the cohort (the "one compile"
        # gauge must be derived, not asserted): the donor build above
        # is the only build path — follower clones and the vmapped jit
        # reuse it; any future path that rebuilds must increment this
        self.engine_builds = 1
        self.build_wall_s = time.time() - t0
        self.tel.gauge("batch.members", len(self.members))
        self.tel.gauge("batch.lifted_consts", list(lift))
        self.tel.gauge("batch.plan", donor.plan.batch_descriptor())
        return self

    def _validate_resumes(self) -> None:
        """Batch-scoped resume guard (ISSUE 19): a member whose
        checkpoint cannot seed THIS cohort's merged layout (a solo
        checkpoint, a different cohort's packing, a torn file) runs
        FRESH instead of failing — lease takeover feeds possibly-stale
        paths by design, so refusal is a downgrade, never an error."""
        from ..engine.ckpt import CkptError, load_checkpoint
        for mem in self.members:
            eng = mem.engine
            path = getattr(eng, "resume_from", None)
            if not path:
                continue
            why = None
            try:
                _, ck = load_checkpoint(path, kind="device")
                if ck.get("module") != mem.model.module.name or \
                        ck.get("vars") != list(mem.model.vars):
                    why = "checkpoint is for a different model"
                elif ck.get("mode") != "host_seen":
                    why = (f"checkpoint was written by the "
                           f"{ck.get('mode')!r} device mode")
                elif ck.get("layout_sig") != eng._layout_sig():
                    why = ("lane layout differs from the checkpoint's "
                           "(solo or different-cohort checkpoint)")
                elif eng.fp_mode and ck.get("key_fn") != KEY_FN:
                    why = ("dedup keys made by another fingerprint "
                           "function (an older jaxmc)")
            except (CkptError, OSError, ValueError) as ex:
                why = str(ex)
            if why is None:
                mem.resumed = True
                continue
            eng.resume_from = None
            self.tel.counter("batch.resume_refused")
            self.log(f"batch member {mem.tag or '?'}: refusing "
                     f"checkpoint {path} ({why}); running fresh")

    # ---- run -----------------------------------------------------------
    def run(self) -> List[BatchMember]:
        """Drive every member's UNCHANGED host_seen loop, one thread per
        member, device work through the shared dispatcher.  Returns the
        members with .result (or .error) filled."""
        assert self.dispatcher is not None, "build() first"
        with self.tel.span("batch.run", members=len(self.members)):
            return self._run()

    def _run(self) -> List[BatchMember]:
        disp = self.dispatcher
        disp.reset()
        for mem in self.members:
            mem.result = mem.error = None
        # serial init prep: tiny, and it primes the shared _host_keys
        # jit buckets so member threads race on dispatch only
        import contextlib
        for mem in self.members:
            eng = mem.engine
            with obs.use_local(mem.tel) if mem.tel is not None \
                    else contextlib.nullcontext():
                eng._prepare_init(time.time(), [])

        def drive(slot: int, mem: BatchMember) -> None:
            eng = mem.engine
            eng._hstep_override = disp.hstep_factory(slot)
            try:
                if mem.tel is not None:
                    with obs.use_local(mem.tel), \
                            mem.tel.span("search", batch_slot=slot):
                        mem.result = eng.run()
                else:
                    mem.result = eng.run()
            except BaseException as ex:  # noqa: BLE001 — the member's
                # failure is ITS verdict; the cohort keeps running
                mem.error = ex
            finally:
                disp.deregister(slot)

        threads = [threading.Thread(
            target=drive, args=(i, mem),
            name=f"jaxmc-batch-m{i}", daemon=True)
            for i, mem in enumerate(self.members)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        self.tel.gauge("batch.occupancy", disp.max_width)
        self.tel.gauge("batch.dispatch_count", disp.dispatches)
        return self.members
