r"""Multi-chip BFS over a jax.sharding.Mesh (SURVEY.md §2.3, §5).

Frontier data-parallelism + fingerprint-space sharding: each device owns
(a) a shard of the frontier, expanded with the SAME compiled kernels as
the single-chip path (compile/kernel2.py — wide layouts, slotted dynamic
\E, capacity buckets), and (b) a hash range of the seen-set, held as
128-bit fingerprints with an explicit validity lane (never in-band
sentinels — a valid state's lane can legitimately equal SENTINEL).

Two exchange strategies route each level's candidates to their owner
shard (chosen per run; `a2a` is the DEFAULT for D > 1,
JAXMC_MESH_EXCHANGE overrides):

  a2a     hash-routes each candidate straight to its owner via
          all_to_all with per-peer buckets of B = C*gamma/D (traffic
          ~C*gamma per device).  The sender sorts its candidates by
          destination and cuts each peer's bucket out of the sorted
          payload as ONE contiguous slice, masked past the run's end
          (ISSUE 31: runs move, never single rows — _place_fn).  Hash
          skew past gamma leaves a run longer than B: its next rows
          are a small per-peer SPILL bucket, the following slice,
          drained by a second all_to_all pass (mesh.a2a_spill); only
          when the spill also overflows is the level rerun with gamma
          doubled (ISSUE 8).
  gather  all_gathers every candidate to every device (traffic C*D per
          device, no routing state); each device keeps the rows whose
          fingerprint lands in its range — the structural analogue of
          ring-partitioned attention state (SURVEY.md §5).

MESH-RESIDENT superstep loop (ISSUE 8 tentpole; ISSUE 10 made the hot
path O(new) and multi-level): the seen shards, the packed frontier and
the per-level trace ring all stay ON DEVICE across levels; one jitted
shard_map dispatch runs up to maxlvl levels in a lax.while_loop — each
level expands, exchanges, RANK-MERGES against the sorted seen shards
(only the valid incoming keys, compacted to a [VC] block, are sorted;
binary searches + row gathers shared with the single-chip resident
engine, bfs._rank_merge — sort work does not scale with the seen
set), appends the trace ring and pushes one replicated [_NS]-i32
scalar vector into a device-side ring.  The host drains that
ring once per superstep (mesh.host_syncs counts SUPERSTEPS, < level
count — no row traffic), pre-sizes nothing, and only pulls rows on a
violation (trace assembly), at a checkpoint, or never.  The loop exits
early on violation / deadlock / assert / kernel overflow / truncation
/ empty frontier, so violation localization, SIGTERM drain and
checkpointing keep their exact level-boundary semantics; capacity
overflows (seen / frontier / trace ring / a2a bucket / valid
candidates) roll the offending level back inside the step, so the host
can grow the named capacity and redo it.  JAXMC_MESH_SUPERSTEP pins
the level budget per dispatch (1 = the one-level escape hatch);
unset, it adapts to measured dispatch wall like the single-chip
resident controller.
Learned capacities (and the settled levels-per-dispatch, MSL) persist
as a profile keyed by (module, layout_sig, D, exchange)
(compile/cache.py variants), so a second mesh run compiles once and
reports window_recompiles == 0.

Refinement and temporal PROPERTYs still check on the mesh via the
LEGACY host loop (the exchanged-candidate stream feeds the same
host-side stepwise refinement and behavior-graph liveness checkers as
the single-chip device modes; store_trace required, resume with
PROPERTYs rejected) — JAXMC_MESH_RESIDENT=0 forces that loop for
diagnosis.

Parity features (VERDICT r2 #5, preserved by the resident loop):
  * counterexample TRACES with action provenance: each kept new-frontier
    row carries its global candidate index (the src lane of the trace
    ring); a violation replays the shortest path exactly like the
    single-chip level mode (store_trace=True, default);
  * NAMED violations: which invariant failed, plus the violating row;
    deadlock/assert report the offending state row the same way;
  * checkpoint/resume at level boundaries (--checkpoint/--resume), the
    TLC states/ equivalent, with full-run count exactness.

The driver validates this path with N virtual CPU devices via
__graft_entry__.dryrun_multichip (no multi-chip hardware needed) on the
raft workload; tests/test_mesh_session.py and tests/test_mesh_resident.py
hold the parity legs on virtual CPU devices, and the benchmark cell
`mesh-recheck-4p` measures the engine on four chips.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from .. import faults
from ..sem.modules import Model
from ..engine.explore import CheckResult, Violation
from ..compile.vspec import ModeError
from ..compile.kernel2 import OV_DEMOTED, OV_PACK
from .bfs import (SENTINEL, TpuExplorer, _LiveGraph, _build_form,
                  _merge_block_rows, _por_mask, _pow2_at_least, _probe_block_rows,
                  _rank_merge, _seen_probe)

_BIG = np.int32(2 ** 31 - 1)

# device-side scalar ring capacity: the superstep while_loop writes one
# [_NS] scalar vector per level into a [_SS_RINGCAP, _NS] ring the host
# drains once per dispatch — the cap bounds levels-per-dispatch (a ring
# entry is 64 bytes, so the whole ring stays trivially small)
_SS_RINGCAP = 64

# the mesh capacity-profile shape (compile/cache.py variant
# "mesh-d<D>-<exchange>"): per-shard seen keys, per-shard frontier rows,
# trace-ring levels, the a2a bucket factor gamma stored as
# round(gamma * 16) so the profile stays integer-valued, MSL — the
# levels-per-dispatch the superstep controller settled on (ISSUE 10),
# so a fresh engine skips the 1 -> 2 -> 4 ramp — and VC, the rank
# merge's learned valid-candidate capacity (ISSUE 11), so a warm run
# skips the VC growth redo too.  Profiles saved before PR 10/11 simply
# lack MSL/VC (hints max-merge, absent keys default).
_MESH_PROFILE_KEYS = ("SC", "FC", "TRL", "GAM16", "MSL")
# optional on READ: profiles saved before PR 11, or by a run whose
# merge never had padding to compact, lack VC — never a reason to drop
# the whole save/load (compile/cache.py)
_MESH_PROFILE_OPT = ("VC",)

# resident-step scalar vector layout (one replicated [NS] i32 vector is
# ALL the host reads per level)
_S_GEN = 0        # psum generated this level
_S_NEW = 1        # psum kept-new (post-constraint) this level
_S_FRONT = 2      # psum next-frontier occupancy
_S_MAXF = 3       # pmax per-shard next-frontier occupancy (true need)
_S_MAXS = 4       # pmax per-shard seen occupancy (true need)
_S_SUMS = 5       # psum seen occupancy
_S_OVC = 6        # pmax kernel overflow code (OV_*; 0 = none)
_S_DEAD = 7       # any deadlocked row (int)
_S_ASSERT = 8     # any failed Assert (int)
_S_INVMIN = 9     # pmin first-violated invariant index (_BIG = none)
_S_FOVF = 10      # frontier outgrew FC (redo after growth)
_S_SOVF = 11      # a seen shard outgrew SC (redo after growth)
_S_TOVF = 12      # trace ring outgrew TRL (redo after growth)
_S_AOVF = 13      # a2a bucket AND spill overflowed (redo, gamma grows)
_S_SPILL = 14     # psum rows drained through the spill pass
_S_MAXDEST = 15   # pmax per-destination bucket occupancy (a2a)
_S_VOVF = 16      # rank merge's valid-candidate block outgrew VC (redo)
_S_MAXV = 17      # pmax per-shard valid-candidate need (grows VC)
_S_PORA = 18      # psum POR singleton-ample states this level (ISSUE 18)
_S_PORX = 19      # psum POR expanded (any-arm-enabled) states this level
_S_PORM = 20      # psum POR-masked candidate rows this level
_S_PROBED = 21    # psum query blocks the shards' rank merges searched
_S_MERGED = 22    # psum blocks of seen2 the shards' rank merges built
_NS = 23

# per-device violation-localization vector (fetched only on violation)
_A_INVW = 0
_A_INVSLOT = 1
_A_DEAD = 2
_A_DEADSLOT = 3
_A_ASSERT = 4
_A_ASRTA = 5
_A_ASRTF = 6
_NA = 7


def _invalid_row(width: int) -> np.ndarray:
    """The row that marks an empty slot of a key or payload table:
    validity lane 1 (sorts last), every data lane SENTINEL."""
    return np.concatenate(
        [np.ones(1, np.int32), np.full(width - 1, SENTINEL, np.int32)])


def _nbytes(*tables) -> int:
    """Bytes of the sharded tables given, over all shards (None: the
    trace ring of a --no-trace run)."""
    return sum(t.nbytes for t in tables if t is not None)


class MeshExplorer(TpuExplorer):
    """BFS with the frontier and seen-set sharded across a device mesh.

    Shares TpuExplorer's whole compile pipeline (layout sampling, slotted
    kernels, compiled invariants/constraints); only the search loop is
    mesh-sharded. Dedup is always on 128-bit fingerprints (the key layout
    the seen shards store)."""

    def __init__(self, model: Model, mesh: Optional[Mesh] = None,
                 log: Callable[[str], None] = None,
                 max_states: Optional[int] = None,
                 progress_every: float = 30.0, store_trace: bool = True,
                 exchange: Optional[str] = None,
                 mesh_caps: Optional[Dict[str, int]] = None, **kw):
        # what this engine cannot honour it refuses BY NAME, before the
        # kernel build is paid for — never dropped silently (ISSUE 26)
        if kw.get("host_seen"):
            raise ModeError(
                "--host-seen is incompatible with the mesh engine "
                "(--devices > 1): its seen set lives in the device "
                "shards, there is no native host store to route to — "
                "drop --host-seen or run on one device")
        if kw.get("resident"):
            raise ModeError(
                "--resident is incompatible with the mesh engine "
                "(--devices > 1): it selects the ONE-chip resident "
                "loop; the mesh's own loop is already device-resident "
                "for plain safety checks — drop --resident (--no-trace "
                "skips the trace ring)")
        super().__init__(model, log=log, max_states=max_states,
                         progress_every=progress_every,
                         store_trace=store_trace, **kw)
        if self.refiners or self.live_obligations:
            # refinement/temporal PROPERTYs run the legacy host loop,
            # which needs the per-level row stream and cannot resume
            if not self.store_trace:
                raise ModeError(
                    "mesh refinement/temporal checking needs the "
                    "per-level row stream: --no-trace is incompatible "
                    "with PROPERTYs on the mesh engine")
            if self.resume_from:
                raise ModeError(
                    "mesh resume with refinement/temporal PROPERTYs is "
                    "not supported - use the single-chip device modes")
        if mesh is None:
            mesh = Mesh(np.array(jax.devices()), ("d",))
        self.mesh = mesh
        self.D = mesh.devices.size
        self._shard0 = NamedSharding(mesh, P("d"))  # see _put
        # re-describe the backend with the ACTUAL mesh extent (the
        # base descriptor reports the whole visible device set): the
        # profile namespace and the mesh shape must describe the mesh
        # this engine actually shards over (ISSUE 11)
        from . import describe_backend
        self.backend_desc = describe_backend(
            platform=self.backend_desc.platform, device_count=self.D)
        # seen shards store fingerprint keys: force fp mode on any width
        # — which means --seen exact cannot be honored here (ISSUE 12):
        # refuse it the way bfs refuses resident/host_seen, instead of
        # silently fingerprinting past the requested contract
        if getattr(self, "seen_mode_req", "auto") == "exact":
            raise ModeError(
                "--seen exact is incompatible with the mesh engine "
                "(seen shards store 128-bit fingerprints) — use the "
                "single-device level mode or --backend interp")
        self.fp_mode = True
        self.K = 4 + 1
        # ICI exchange strategy (SURVEY.md §2.3 "communication
        # scheduling"): a2a is the default whenever the mesh has more
        # than one device — its traffic is ~C*gamma per device instead
        # of gather's C*D, and the spill pass makes hash skew cheap.
        # JAXMC_MESH_EXCHANGE overrides; an explicit constructor arg
        # outranks both (tests pin each strategy).
        self._exchange_src = "explicit"
        if exchange is None:
            env = os.environ.get("JAXMC_MESH_EXCHANGE", "").strip()
            if env:
                exchange, self._exchange_src = env, "JAXMC_MESH_EXCHANGE"
            else:
                exchange = "a2a" if self.D > 1 else "gather"
                self._exchange_src = "default"
        if exchange not in ("gather", "a2a"):
            raise ValueError(f"exchange must be 'gather' or 'a2a', "
                             f"got {exchange!r}")
        self.exchange = exchange
        # levels per resident dispatch (ISSUE 10 supersteps):
        # JAXMC_MESH_SUPERSTEP=<n> pins it (1 = the one-level-per-
        # dispatch escape hatch); unset/auto adapts to measured
        # dispatch wall like the single-chip resident maxlvl
        # controller.
        ss = os.environ.get("JAXMC_MESH_SUPERSTEP", "").strip().lower()
        self._ss_fixed: Optional[int] = None
        if ss not in ("", "0", "auto"):
            try:
                self._ss_fixed = max(1, min(int(ss), _SS_RINGCAP))
            except ValueError:
                self._ss_fixed = None
        # GROUPED expansion (ISSUE 11: PR 7's fused arm groups ported
        # onto the mesh expand path): on XLA:CPU a single jit holding
        # every kernel instance compiles superlinearly (the host_seen
        # engine has split at JAXMC_FUSED_MAX_INSTANCES since ISSUE 7),
        # and the all-inline mesh step hit exactly that wall on
        # many-instance models.  When it would, the resident level runs
        # as ceil(A/fused_max) shard_map'd GROUP expansion dispatches
        # feeding one merge/tail dispatch — candidate order (groups are
        # contiguous in self.compiled order and concatenate in order)
        # and therefore counts/traces stay bit-identical.  One level
        # per dispatch: the group boundary is a host hop, so supersteps
        # cannot fuse across it.  JAXMC_MESH_GROUPED=1/0 forces it
        # either way (tests pin parity with the fused step).
        self._mesh_fused_max = int(os.environ.get(
            "JAXMC_FUSED_MAX_INSTANCES", "24"))
        genv = os.environ.get("JAXMC_MESH_GROUPED", "").strip()
        if genv in ("0", "1"):
            self._grouped = genv == "1"
        else:
            self._grouped = (self.backend_desc.platform == "cpu"
                             and self.A > self._mesh_fused_max)
        if self._grouped:
            self._ss_fixed = 1
        self._mesh_maxlvl_warm = 1  # learned levels-per-dispatch ramp
        self._ss_shrunk = False     # controller ever had to halve?
        self._supersteps = 0
        self._superstep_levels_max = 0
        self._a2a_gamma = 2.0
        self._mesh_step_cache: Dict[Tuple, Callable] = {}
        # skewed-hash fault site (ISSUE 8 satellite): when armed, EVERY
        # state hashes to shard 0 — on both the host init-shard path and
        # the device routing (one owner formula, so they cannot
        # disagree) — forcing the a2a spill pass (and, once the spill
        # overflows, the gamma-doubling rerun) on models far too small
        # to skew naturally.  Counts/traces must stay exact throughout;
        # tests/test_mesh_resident.py pins it.
        self._skew = faults.fire("mesh_skew", devices=self.D) is not None
        # observed per-shard valid-candidate need (max of the scalar
        # ring's _S_MAXV across committed levels): what the durable
        # profile saves as VC (ISSUE 11) — the lean size the NEXT
        # process warm-starts at — while the in-process capacity stays
        # at whatever this run grew to (shrinking it mid-process would
        # recompile the warm window).  Deliberately NOT reset per run.
        self._vc_seen_need = 0
        # resident-loop accounting (ISSUE 8 obs satellite)
        self._spill_rows = 0
        self._max_bucket = 0
        self._shard_balance: Optional[float] = None
        self._lvl_FC: List[int] = []   # expanding FC per ring level
        # learned mesh capacity profile, keyed (module, layout_sig, D,
        # exchange): a second mesh run starts at the learned caps and
        # gamma, so its one warm-up compile covers the run
        # (window_recompiles == 0).  Max-merged with the caller's
        # manifest hint (corpus.Case.mesh_caps).
        self._mesh_caps_hint: Dict[str, int] = dict(mesh_caps or {})
        if self.cap_profile:
            from ..compile.cache import load_capacity_profile
            prof = load_capacity_profile(
                model.module.name, self._layout_sig(),
                variant=self._profile_variant(),
                keys=_MESH_PROFILE_KEYS, optional=_MESH_PROFILE_OPT)
            if prof:
                for kk, vv in prof.items():
                    self._mesh_caps_hint[kk] = max(
                        int(self._mesh_caps_hint.get(kk, 0)), int(vv))
        if self._mesh_caps_hint.get("GAM16"):
            self._a2a_gamma = max(
                self._a2a_gamma, self._mesh_caps_hint["GAM16"] / 16.0)
        if self._mesh_caps_hint.get("MSL"):
            self._mesh_maxlvl_warm = max(
                self._mesh_maxlvl_warm,
                min(int(self._mesh_caps_hint["MSL"]), _SS_RINGCAP))

    def _profile_variant(self) -> str:
        # namespaced by backend platform (ISSUE 11): a TPU mesh's
        # learned caps must never warm a cpu-XLA virtual-device run
        return self.backend_desc.profile_variant(
            f"mesh-d{self.D}-{self.exchange}")

    # ---- hierarchical seen set (ISSUE 12): per-shard tiering ----

    def _mesh_shard_cap(self) -> Optional[int]:
        """Per-shard device seen cap: the engine cap (--seen-cap /
        JAXMC_SEEN_CAP, TOTAL device key rows) divided across the D
        owner-routed shards."""
        if self.seen_cap is None:
            return None
        return _pow2_at_least(max(self.seen_cap // self.D, 64), lo=64)

    def _mesh_tier_spill(self, seen, seen_count, SC: int):
        """Spill every shard's sorted valid prefix into the cold tiers
        as one immutable run each — owner-routed keys PARTITION the key
        space, so a single combined store answers membership for every
        shard — and restart the shards empty.  Returns the reset
        (seen, seen_count) device pair."""
        tel = obs.current()
        scounts = np.asarray(seen_count)
        total = int(scounts.sum())
        with tel.span("tier.spill", keys=total, shards=self.D,
                      bytes=int(seen.nbytes)):
            seen_np = np.asarray(seen)
            t = self._ensure_tiers()
            for dd in range(self.D):
                cnt = int(scounts[dd])
                if cnt:
                    t.spill(np.ascontiguousarray(
                        seen_np[dd, :cnt, 1:]))
            tel.counter("tier.spilled_keys", total)
        return (self._mesh_table((self.D, SC, self.K),
                                 fill=_invalid_row(self.K)),
                self._put(np.zeros(self.D, np.int32)))

    def _mesh_tier_filter(self, frontier, fcount, tr_rows, tr_src,
                          depth: int, FC: int):
        """Post-commit cold-tier filter for one mesh level (supersteps
        are pinned to 1 while tiering is active): drop frontier rows
        whose keys live in the host/disk runs — per shard, order-
        preserving — and rewrite the level's trace-ring slot with the
        SAME compaction so parent indices recorded by the next level
        keep resolving.  Returns (frontier, fcount, tr_rows, tr_src,
        n_dup)."""
        tel = obs.current()
        with tel.span("tier.pull", rows=self.D * FC):
            fr_np = np.asarray(frontier)          # [D, FC, PW]
            fc_np = np.asarray(fcount).astype(np.int32).copy()
        keeps = []
        n_dup = 0
        for dd in range(self.D):
            c = int(fc_np[dd])
            if c == 0:
                keeps.append(None)
                continue
            keep = self._tier_keep_mask(fr_np[dd, :c])
            keeps.append(keep)
            n_dup += int((~keep).sum())
        if n_dup == 0:
            return frontier, fcount, tr_rows, tr_src, 0
        new_fr = np.full_like(fr_np, SENTINEL)
        new_src = None
        src_slot = None
        if self.store_trace:
            src_slot = np.asarray(tr_src[:, depth - 1])
            new_src = np.full((self.D, FC), -1, np.int32)
            tel.counter("mesh.row_syncs")
        for dd in range(self.D):
            c = int(fc_np[dd])
            if c == 0:
                continue
            keep = keeps[dd]
            k = int(keep.sum())
            new_fr[dd, :k] = fr_np[dd, :c][keep]
            if new_src is not None:
                new_src[dd, :k] = src_slot[dd, :c][keep]
            fc_np[dd] = k
        with tel.span("tier.push", rows=int(fc_np.sum())):
            frontier = self._put(new_fr)
            fcount = self._put(fc_np)
            if self.store_trace:
                tr_rows = tr_rows.at[:, depth - 1].set(self._put(new_fr))
                tr_src = tr_src.at[:, depth - 1].set(self._put(new_src))
        return frontier, fcount, tr_rows, tr_src, n_dup

    # ---- the sharded level step ----
    def _a2a_bucket(self, C: int, FC: int) -> int:
        import math
        # floor: R = D*B must cover the frontier capacity FC, or a
        # sparse no-overflow level could hand the next step a frontier
        # narrower than its compiled shape (review r3)
        return max(1, math.ceil(C * self._a2a_gamma / self.D),
                   math.ceil(FC / self.D))

    def _a2a_spill_bucket(self, B: int) -> int:
        # the spill bucket is deliberately small: it exists to absorb
        # ordinary hash skew (a few rows past B on a hot shard), not to
        # double capacity — B//4 keeps the second all_to_all cheap
        return max(1, B // 4)

    def _owner_from_keys(self, keys: np.ndarray) -> np.ndarray:
        """THE ownership formula (keys lane 1 mod D) — one definition
        for every host path; _owner_jnp is its device-side twin (both
        routes call it, so host and device can never disagree).  The
        mesh_skew fault collapses it to shard 0 on BOTH paths."""
        if self._skew:
            return np.zeros(len(keys), np.int64)
        return (keys[:, 1].astype(np.uint32) % np.uint32(self.D)) \
            .astype(np.int64)

    def _owner_jnp(self, key_lane1):
        """Device-side twin of _owner_from_keys over the keys' lane-1
        column (traced int32 [N]) — the ONLY place the exchange
        closures compute ownership."""
        if self._skew:
            return jnp.zeros(key_lane1.shape[0], jnp.int32)
        return (key_lane1.astype(jnp.uint32)
                % jnp.uint32(self.D)).astype(jnp.int32)

    def _place_fn(self, C: int, B: int, SB: int) -> Callable:
        """The a2a route's sender side: place(ckeys [C,K], cand [C,PW],
        cvalid [C], me) -> (b1 [D,B,Pw], b2 [D,SB,Pw], spill_local,
        a2a_ovf_local, maxdest_local) — the per-peer buckets and SPILL
        buckets the two all_to_alls send, free slots holding the
        invalid row [1, SENTINEL...].  It holds no collective.

        The route moves RUNS, not rows (ISSUE 31): the stable sort by
        destination leaves peer d's rows as the contiguous run
        [excl[d], excl[d] + counts[d]) of the payload, in candidate
        order, so bucket d is that run's first B rows and spill bucket
        d its next SB — one slice each and a mask past the run's end,
        never a per-row scatter (~60 ns a row on the TPU v5e against a
        streamed copy; PERF.md §6, PR 31).  Rows past B + SB of a run
        are dropped and reported (a2a_ovf): the level is redone with a
        larger gamma."""
        D, K, PW = self.D, self.K, self.PW
        Pw = K + PW + 1  # a2a payload: [keys | packed row | src-index]
        invalid_row_np = _invalid_row(Pw)

        @jax.named_scope("jaxmc.mesh.route")
        def place(ckeys, cand, cvalid, me):
            # bucket-sort by destination (invalid candidates sort
            # last, destination D); traffic per device: D*(B+SB) =
            # ~C*gamma rows instead of gather's C*D
            dest = jnp.where(cvalid, self._owner_jnp(ckeys[:, 1]), D)
            sperm = lax.sort(
                (dest, jnp.arange(C, dtype=jnp.int32)),
                num_keys=1, is_stable=True)[1]
            # run borders by compare-and-sum: excl[d] candidates have a
            # destination below d (excl[D] = the valid ones)
            excl = jnp.sum(
                dest[:, None] < jnp.arange(D + 1, dtype=jnp.int32)[None],
                axis=0, dtype=jnp.int32)
            counts = excl[1:] - excl[:-1]                  # [D]
            # overflow only when bucket AND spill are exhausted; the
            # max per-destination occupancy rides the scalar vector so
            # the host can grow gamma straight to the observed need
            # (one rerun, not log2 doublings)
            a2a_ovf = jnp.any(counts > B + SB)
            spill_local = jnp.sum(
                jnp.clip(counts - B, 0, SB)).astype(jnp.int32)
            maxdest_local = jnp.max(counts).astype(jnp.int32)
            srcid = me.astype(jnp.int32) * C + sperm
            invalid_row = jnp.asarray(invalid_row_np)
            # B + SB invalid rows behind the payload: no slice start is
            # ever clamped (a clamped start would shift a run)
            payload = jnp.concatenate(
                [jnp.concatenate(
                    [jnp.take(ckeys, sperm, axis=0),
                     jnp.take(cand, sperm, axis=0),
                     srcid[:, None]], axis=1),
                 jnp.broadcast_to(invalid_row, (B + SB, Pw))])

            def cut(start, n, live):
                rows = lax.dynamic_slice(payload, (start, 0), (n, Pw))
                return jnp.where(
                    jnp.arange(n, dtype=jnp.int32)[:, None] < live,
                    rows, invalid_row)

            b1 = jnp.stack([cut(excl[d], B, jnp.minimum(counts[d], B))
                            for d in range(D)])            # [D, B, Pw]
            b2 = jnp.stack([cut(excl[d] + B, SB,
                                jnp.clip(counts[d] - B, 0, SB))
                            for d in range(D)])            # [D, SB, Pw]
            return b1, b2, spill_local, a2a_ovf, maxdest_local

        return place

    def _route_fn(self, C: int, FC: int) -> Tuple[Callable, int, int, int]:
        """Build the exchange closure shared by the legacy, resident
        and grouped steps: route(ckeys, cand, cvalid, me) ->
        (gkeys [R,K], gcand [R,PW], gsrc [R], spill_local,
        a2a_ovf_local, maxdest_local, evalid [R]).  a2a: the sender's
        buckets come from _place_fn (slices of the destination-sorted
        payload), two all_to_alls swap them, the receiver unpacks.
        `evalid` is the EDGE-STREAM validity — every valid exchanged
        row BEFORE ownership masking (gather replicates the full
        candidate set, so the host's device-0 read must not lose
        foreign-owned rows; a2a buckets are disjoint per device and the
        host concatenates all of them, so per-device validity is
        already complete).  Returns (route, R, B, SB); B/SB are 0 in
        gather mode."""
        D, K, PW = self.D, self.K, self.PW
        a2a = self.exchange == "a2a"
        Pw = K + PW + 1  # a2a payload: [keys | packed row | src-index]
        invalid_key_np = _invalid_row(K)
        if not a2a:
            R = D * C

            def route_gather(ckeys, cand, cvalid, me):
                invalid_key = jnp.asarray(invalid_key_np)
                # ICI exchange: gather all candidates + keys, keep my
                # range
                with jax.named_scope("jaxmc.mesh.exchange"):
                    gcand = lax.all_gather(cand, "d", tiled=True)
                    gkeys = lax.all_gather(ckeys, "d", tiled=True)
                with jax.named_scope("jaxmc.mesh.route"):
                    gsrc = jnp.arange(R, dtype=jnp.int32)
                    gvalid = gkeys[:, 0] == 0  # explicit validity lane
                    owner = self._owner_jnp(gkeys[:, 1])
                    mine = gvalid & (owner == me)
                    # foreign/invalid rows: validity lane 1 (sorts
                    # last), data lanes sentinel so equal keys cannot
                    # straddle the mask
                    gkeys = jnp.where(mine[:, None], gkeys, invalid_key)
                zero = jnp.zeros((), jnp.int32)
                return (gkeys, gcand, gsrc, zero, jnp.asarray(False),
                        zero, gvalid)

            return route_gather, R, 0, 0

        B = self._a2a_bucket(C, FC)
        SB = self._a2a_spill_bucket(B)
        R = D * (B + SB)
        place = self._place_fn(C, B, SB)

        @jax.named_scope("jaxmc.mesh.exchange")
        def swap(b1, b2):
            invalid_key = jnp.asarray(invalid_key_np)
            recv1 = lax.all_to_all(
                b1, "d", split_axis=0, concat_axis=0).reshape(D * B, Pw)
            recv2 = lax.all_to_all(
                b2, "d", split_axis=0, concat_axis=0).reshape(D * SB, Pw)
            recv = jnp.concatenate([recv1, recv2])     # [R, Pw]
            gkeys = recv[:, :K]
            gcand = recv[:, K:K + PW]
            gsrc = recv[:, K + PW]
            gvalid = gkeys[:, 0] == 0
            # routed rows are mine by construction; invalid slots keep
            # the sorts-last key shape
            gkeys = jnp.where(gvalid[:, None], gkeys, invalid_key)
            return gkeys, gcand, gsrc, gvalid

        def route_a2a(ckeys, cand, cvalid, me):
            b1, b2, spill_local, a2a_ovf, maxdest_local = place(
                ckeys, cand, cvalid, me)
            gkeys, gcand, gsrc, gvalid = swap(b1, b2)
            return (gkeys, gcand, gsrc, spill_local, a2a_ovf,
                    maxdest_local, gvalid)

        return route_a2a, R, B, SB

    def _exchange_bytes(self, C: int, B: int, SB: int) -> int:
        """Whole-mesh bytes moved by one level's exchange (host-side,
        from the static shapes): a2a moves D*(B+SB) payload rows of
        K+PW+1 words per device; gather replicates C candidate+key rows
        to every device."""
        D, K, PW = self.D, self.K, self.PW
        if self.exchange == "a2a":
            return D * D * (B + SB) * (K + PW + 1) * 4
        return D * D * C * (K + PW) * 4

    @property
    def _finish_form(self) -> str:
        """`prefix` | `scatter`: how _merge_finish_fn compacts the
        explore-kept rows — decided by whether the model has a
        CONSTRAINT, nothing else (gauge mesh.finish_form)."""
        return "scatter" if self.constraint_fns else "prefix"

    def _merge_finish_fn(self, R: int):
        """Shared merge epilogue: constraint-mask the compacted new
        rows and compact the explore-kept ones to the frontier front.
        Constraints FIRST: violating states stay fingerprinted in the
        seen shard but are discarded — not distinct, not checked, not
        explored (TLC semantics, testout2:265).

        Two forms, chosen here from the model alone (ISSUE 33):

        * `prefix` — no CONSTRAINT in the cfg: the kept rows are
          `arange(R) < new_count`, ONE run that already sits at the
          front, so the compaction is the identity and only the tail
          past front_count is put in the empty form (SENTINEL rows, 0
          src).  No scatter.
        * `scatter` — with a CONSTRAINT the kept rows are not a run:
          the cumsum-rank scatter of ISSUE 11 (the 1-key stable sort it
          replaced was a measurable slice of the merge wall at mesh
          shapes).  The kept-row ORDER is the input order, as the
          stable sort's was.

        Both leave the same four outputs to the bit where both apply;
        the tail is SENTINEL rows / 0 src, which every consumer
        already masks by front_count."""
        plan = self.plan
        con_fns = self.constraint_fns
        inv_fns = self.inv_fns

        @jax.named_scope("jaxmc.compact")
        def finish_prefix(new_rows, new_src, nvalid):
            # new_rows is already SENTINEL past new_count (the caller's
            # take is masked); the unpacked twin and src are not
            front_rows_u = new_rows
            if inv_fns:
                with jax.named_scope("jaxmc.scan"):
                    front_rows_u = jnp.where(
                        nvalid[:, None], plan.unpack_rows(new_rows),
                        SENTINEL)
            front_src = jnp.where(nvalid, new_src, 0)
            return (new_rows, front_rows_u, front_src,
                    jnp.sum(nvalid, dtype=jnp.int32))

        @jax.named_scope("jaxmc.compact")
        def finish_scatter(new_rows, new_src, nvalid):
            with jax.named_scope("jaxmc.constraint"):
                new_rows_u = plan.unpack_rows(new_rows)
                explore = nvalid
                for nm, f in con_fns:
                    explore = explore & jax.vmap(f)(new_rows_u)
            idx4 = jnp.arange(R, dtype=jnp.int32)
            pos = jnp.cumsum(explore.astype(jnp.int32)) - 1
            tgt = jnp.where(explore, pos, R + idx4)
            front_rows = jnp.full((R, new_rows.shape[1]), SENTINEL,
                                  jnp.int32) \
                .at[tgt].set(new_rows, mode="drop", unique_indices=True)
            front_rows_u = jnp.full((R, new_rows_u.shape[1]), SENTINEL,
                                    jnp.int32) \
                .at[tgt].set(new_rows_u, mode="drop",
                             unique_indices=True)
            front_src = jnp.zeros((R,), jnp.int32) \
                .at[tgt].set(new_src, mode="drop", unique_indices=True)
            front_count = jnp.sum(explore)
            return front_rows, front_rows_u, front_src, front_count

        return finish_scatter if con_fns else finish_prefix

    @property
    def _compact_form(self) -> str:
        """`runs` | `scatter`: how _merge_rank_fn compacts the valid
        received rows — decided by the exchange's kind, nothing else
        (gauge mesh.compact_form)."""
        return "runs" if self.exchange == "a2a" else "scatter"

    def _compact_runs_fn(self, B: int, SB: int, VC: int) -> Callable:
        """The a2a receiver's valid-candidate compaction (ISSUE 33):
        compact(gkeys [R,K], gcand [R,PW], gsrc [R]) -> (ckeys [VC,K],
        ccand [VC,PW], csrc [VC], v_need) — the valid rows of the
        received block in received order, then the empty form
        ([1, SENTINEL...] / SENTINEL / 0).

        The receiver moves RUNS, not rows.  The block _route_fn's
        swap() hands over is D buckets of B rows and then D spill
        buckets of SB rows, and each of those 2*D segments left the
        sender's place() as its `live` valid rows followed by invalid
        rows — a valid prefix and padding, by construction.  So the
        stable compaction is the concatenation of 2*D prefixes: segment
        s goes, as ONE slice of its first min(len, VC) rows, to the
        offset that is the valid count of the segments before it, and
        each later segment overwrites the padding the previous one
        brought along — never a per-row scatter (5-10 ns a received
        slot on the TPU v5e, ~94% of them dropped; PERF.md §6, PR 33).
        The rows a segment carries past its count are already in the
        empty form, so nothing is masked but the src column (its
        padding is SENTINEL on the wire, 0 in the block).

        v_need > VC rolls the level back (the caller's v_ovf), so what
        the block holds then does not matter; offsets are capped at VC,
        where the buffer keeps a segment's length of slack that the
        crop drops — no start is ever clamped back into live rows."""
        D = self.D
        segs = [(d * B, B) for d in range(D)] + \
            [(D * B + d * SB, SB) for d in range(D)]
        # what is cut out of a segment: its first min(len, VC) rows
        cuts = [(s, min(n, VC)) for s, n in segs]
        slack = max(n for _, n in cuts)

        def compact(gkeys, gcand, gsrc):
            gvalid = gkeys[:, 0] == 0
            counts = jnp.stack([
                jnp.sum(gvalid[s:s + n], dtype=jnp.int32)
                for s, n in segs])
            v_need = jnp.sum(counts)
            offs = jnp.minimum(jnp.cumsum(counts) - counts, VC)

            def runs(col, empty):
                buf = jnp.broadcast_to(
                    jnp.asarray(empty, jnp.int32),
                    (VC + slack,) + col.shape[1:])
                for i, (s, n) in enumerate(cuts):
                    buf = lax.dynamic_update_slice_in_dim(
                        buf, col[s:s + n], offs[i], 0)
                return buf[:VC]

            # the packed rows go lane by lane: their consumer is a row
            # gather, which made XLA:TPU lay a [VC + slack, PW] buffer
            # out row-major, PW lanes padded to 128, and every slice
            # paid for it (PERF.md §6, PR 33); a 1-D lane has one
            # layout, and the stack is one pass over VC rows
            ccand = jnp.stack([runs(gcand[:, j], SENTINEL)
                               for j in range(gcand.shape[1])], axis=1)
            csrc = jnp.where(jnp.arange(VC, dtype=jnp.int32) < v_need,
                             runs(gsrc, 0), 0)
            return (runs(gkeys, _invalid_row(gkeys.shape[1])), ccand,
                    csrc, v_need)

        return compact

    def _compact_scatter_fn(self, R: int, VC: int) -> Callable:
        """The valid-candidate compaction of a block with no run
        structure (the `gather` exchange: all D*C candidate rows,
        masked by owner): a cumsum-rank scatter — stable, so candidate
        order and therefore counts/traces are those of the uncompacted
        sort.  Same signature and output as _compact_runs_fn."""
        K, PW = self.K, self.PW

        def compact(gkeys, gcand, gsrc):
            gvalid = gkeys[:, 0] == 0
            v_need = jnp.sum(gvalid, dtype=jnp.int32)
            pos = jnp.cumsum(gvalid.astype(jnp.int32)) - 1
            # invalid rows park at R+i: distinct, >= VC (dropped), and
            # disjoint from every valid pos (pos <= R-1) even when
            # v_need > VC — duplicate indices, dropped or not, would
            # break the unique_indices promise below
            tgt = jnp.where(gvalid, pos,
                            R + jnp.arange(R, dtype=jnp.int32))
            ck = jnp.full((VC, K), SENTINEL, jnp.int32)
            ck = ck.at[:, 0].set(1)  # empty: validity lane 1
            ckeys = ck.at[tgt].set(gkeys, mode="drop",
                                   unique_indices=True)
            ccand = jnp.full((VC, PW), SENTINEL, jnp.int32) \
                .at[tgt].set(gcand, mode="drop", unique_indices=True)
            csrc = jnp.zeros((VC,), jnp.int32) \
                .at[tgt].set(gsrc, mode="drop", unique_indices=True)
            return ckeys, ccand, csrc, v_need

        return compact

    def _merge_rank_fn(self, SC: int, R: int, VC: Optional[int] = None,
                       B: int = 0, SB: int = 0) -> Callable:
        """The shard-local merge-dedup of every step builder:
        (seen_keys [SC,K], seen_count scalar, gkeys [R,K], gcand [R,PW],
        gsrc [R]) -> dict(seen2, seen_count2, front_rows, front_rows_u,
        front_src, front_count, new_count, v_ovf, v_need, probe_blocks,
        merge_blocks).

        O(new) and O(valid): the exchanged block is ~95% masked padding
        (its 5-key sort over all R rows was 11.6s of a 25s step wall on
        transfer_scaled D=1 — XLA:CPU, round r07), so the valid rows are
        first compacted to a [VC]-bounded block — stably, so candidate
        order and therefore counts/traces are unchanged — then only
        those keys are sorted, deduped against
        the seen shard's sorted valid prefix with binary searches and
        merged in by rank (row gathers into the blocks of the shard's
        table that hold a live row after the level: each shard bounds
        its own loops with its own counts) — the single-chip resident
        engine's merge (bfs._rank_merge), shared rather than
        duplicated; single-key-safe ops only, so the superstep
        while_loop can wrap it.  seen_count2 is the TRUE per-shard need
        BEFORE any [:SC] crop, which the resident loop grows SC to;
        constraint-discarded states stay fingerprinted but are never
        counted, checked or explored (TLC semantics).

        The compaction has two forms, chosen from the exchange's kind
        when the program is built (_compact_form; ISSUE 33): after an
        a2a exchange the received block is 2*D valid prefixes of known
        segments (`B`, `SB`: _route_fn's), and the block is built from
        2*D slices (`runs`, _compact_runs_fn); the gather exchange's
        block has no such structure and keeps the cumsum-rank scatter
        (`scatter`, _compact_scatter_fn).  The output block is the
        same to the bit.

        `VC` is the valid-candidate capacity: overflow (`v_ovf`, with
        `v_need` the true count) rolls the level back so the caller
        can grow VC and redo — same contract as every other mesh
        capacity.  The resident and grouped steps always pass one
        (_initial_vc).  VC=None sorts all R rows uncompacted and is
        the LEGACY exchange step's form alone (_get_mesh_step: the
        host loop for PROPERTYs, and multihost.py), which has no
        grow-and-redo for it; VC >= R has nothing to compact
        either."""
        compact = VC is not None and VC < R
        N = VC if compact else R
        finish = self._merge_finish_fn(N)
        if compact:
            compact_fn = self._compact_runs_fn(B, SB, VC) \
                if self._compact_form == "runs" \
                else self._compact_scatter_fn(R, VC)

        def merge(seen_keys, seen_count, gkeys, gcand, gsrc):
            v_ovf = jnp.asarray(False)
            v_need = jnp.asarray(0, jnp.int32)
            if compact:
                with jax.named_scope("jaxmc.compact"):
                    gkeys, gcand, gsrc, v_need = compact_fn(
                        gkeys, gcand, gsrc)
                    v_ovf = v_need > VC
            rm = _rank_merge(seen_keys, seen_count, gkeys, N, SC, self.K,
                             multikey=True)
            new_count = rm["new_count"]
            nvalid = jnp.arange(N) < new_count
            with jax.named_scope("jaxmc.compact"):
                safe = jnp.clip(rm["nk_sidx"], 0, N - 1)
                new_rows = jnp.take(gcand, safe, axis=0)
                new_src = jnp.take(gsrc, safe)
                new_rows = jnp.where(nvalid[:, None], new_rows,
                                     SENTINEL)
            front_rows, front_rows_u, front_src, front_count = \
                finish(new_rows, new_src, nvalid)
            return dict(seen2=rm["seen2"],
                        seen_count2=rm["seen_count2"],
                        front_rows=front_rows, front_rows_u=front_rows_u,
                        front_src=front_src, front_count=front_count,
                        new_count=new_count, v_ovf=v_ovf, v_need=v_need,
                        probe_blocks=rm["probe_blocks"],
                        merge_blocks=rm["merge_blocks"])

        return merge

    def _initial_vc(self, FC: int) -> int:
        """The rank merge's starting valid-candidate capacity (ISSUE
        11): the learned profile value when one exists, else 4*FC —
        generously above the typical valid-row count routed to one
        shard (revisits included), so most runs never pay the growth
        redo, while staying far under R's ~95% padding.  Always >= FC
        (the committed frontier is cropped to [FC] from the compacted
        block)."""
        hint = int(self._mesh_caps_hint.get("VC", 0))
        if hint:
            # a learned profile records the OBSERVED need (pow2-rounded
            # at save): trust it instead of flooring at 4*FC — the
            # whole point of the compaction is sorting the ~FC valid
            # rows, not R's padding, and an underestimate only costs
            # one growth redo
            return max(FC, _pow2_at_least(hint, lo=256))
        return max(FC, _pow2_at_least(4 * FC, lo=256))

    def _merge_out_rows(self, R: int, VC: int) -> int:
        """Row count of the merge's compacted output block: VC, unless
        the whole exchanged block is no larger."""
        return min(VC, R)

    @jax.named_scope("jaxmc.scan")
    def _inv_scan(self, front_rows_u, front_count, R: int):
        """Named invariants: index of the FIRST cfg invariant any kept
        row violates, plus the first violating slot."""
        frontvalid = jnp.arange(R) < front_count
        inv_which = jnp.int32(_BIG)
        inv_slot = jnp.int32(-1)
        for i, (nm, f) in enumerate(self.inv_fns):
            bad = frontvalid & ~jax.vmap(f)(front_rows_u)
            anyb = jnp.any(bad)
            hit = anyb & (inv_which == _BIG)
            inv_which = jnp.where(hit, jnp.int32(i), inv_which)
            inv_slot = jnp.where(hit,
                                 jnp.argmax(bad).astype(jnp.int32),
                                 inv_slot)
        return inv_which, inv_slot

    def _get_mesh_step(self, SC: int, FC: int,
                       out_cap: Optional[int] = None) -> Callable:
        """The LEGACY exchange step: out_cap=None drives the host-loop
        modes (refinement/temporal PROPERTYs — _run_hostloop); out_cap
        set is the MULTI-HOST variant (tpu/multihost.py): the new
        frontier is cropped on device to a fixed [out_cap] shard so the
        host never needs non-addressable remote rows, and extra
        REPLICATED flags (psum'd over the DCN+ICI axis) are appended to
        the outputs: any_inv, fixed_ovf (a frontier/seen shard outgrew
        its fixed capacity, incl. a2a bucket+spill overflow), any_dead,
        any_assert."""
        C = self.A * FC
        route, R, B, SB = self._route_fn(C, FC)
        key = (SC, FC, B, SB, out_cap)
        if key in self._mesh_step_cache:
            return self._mesh_step_cache[key]
        K, D, PW = self.K, self.D, self.PW
        plan = self.plan
        con_fns = self.constraint_fns
        block_fn = self._candidate_block_fn(FC)
        merge_fn = self._merge_rank_fn(SC, R)
        # refinement/temporal PROPERTYs: stream every exchanged
        # candidate (revisits included) to the host, which runs the SAME
        # stepwise refinement and behavior-graph checkers as the
        # single-chip device modes (r4; closes VERDICT r3 #9)
        need_edges = (out_cap is None and
                      (bool(self.refiners) or self.collect_edges))

        def device_step(seen_keys, seen_count, frontier_p, fcount):
            # per-device blocks: seen_keys [SC,K], seen_count [1],
            # frontier [FC,PW], fcount [1]
            seen_keys = seen_keys.reshape(SC, K)
            frontier = plan.unpack_rows(frontier_p.reshape(FC, PW))
            me = lax.axis_index("d")
            fvalid = jnp.arange(FC) < fcount[0]
            blk = block_fn(frontier, fvalid)
            overflow = blk["overflow"]
            dead = blk["dead"]
            dead_local = jnp.any(dead)
            dead_slot = blk["dead_slot"]
            assert_bad = blk["assert_bad"]
            asrt_a, asrt_f = blk["asrt_a"], blk["asrt_f"]
            gen_local = blk["gen_local"]

            (gkeys, gcand, gsrc, spill_local, a2a_ovf, _maxdest,
             evalid) = route(blk["ckeys"], blk["cand"], blk["cvalid"],
                             me)

            mg = merge_fn(seen_keys, seen_count[0], gkeys, gcand, gsrc)
            seen2 = mg["seen2"]
            seen_count2 = mg["seen_count2"]
            front_rows = mg["front_rows"]
            front_rows_u = mg["front_rows_u"]
            front_src = mg["front_src"]
            front_count = mg["front_count"]
            inv_which, inv_slot = self._inv_scan(front_rows_u,
                                                 front_count, R)

            # global totals over ICI; violation flags stay PER-DEVICE so
            # the host can locate the offending device's row/provenance
            tot_gen = lax.psum(gen_local, "d")
            tot_new = lax.psum(front_count, "d")
            any_ovf = lax.pmax(overflow, "d")  # 0 = none, else max OV_*
            tot_front = lax.psum(front_count, "d")
            tot_spill = lax.psum(spill_local, "d")

            any_a2a_ovf = lax.psum(a2a_ovf.astype(jnp.int32), "d") > 0
            if out_cap is not None:
                # multi-host: fixed-capacity frontier shard + replicated
                # abort flags — the host loop reads ONLY replicated
                # scalars and its own addressable shards. a2a bucket+
                # spill overflow folds into the fixed-capacity abort
                # (the multi-host loop cannot re-run a level, so it
                # aborts loudly instead of retrying with a larger
                # gamma).
                fixed_ovf = lax.psum(
                    ((front_count > out_cap) | (seen_count2 > SC) |
                     a2a_ovf).astype(jnp.int32), "d") > 0
                any_inv = lax.psum(
                    (inv_which != _BIG).astype(jnp.int32), "d") > 0
                any_dead = lax.psum(
                    dead_local.astype(jnp.int32), "d") > 0
                any_assert = lax.psum(
                    assert_bad.astype(jnp.int32), "d") > 0
                # indices 0-11 are the r4 surface; 12-19 add PER-DEVICE
                # provenance (each process reads only its own shards) so
                # the multi-host loop can assemble exact counterexample
                # traces via the process-allgather protocol
                # (multihost.py, VERDICT r4 #7); 20 is the psum'd spill
                # row count (ISSUE 8)
                return (seen2.reshape(1, SC, K), seen_count2.reshape(1),
                        front_rows[:out_cap].reshape(1, out_cap, PW),
                        front_count.reshape(1),
                        tot_gen.reshape(1), tot_new.reshape(1),
                        any_ovf.reshape(1), tot_front.reshape(1),
                        fixed_ovf.reshape(1), any_inv.reshape(1),
                        any_dead.reshape(1), any_assert.reshape(1),
                        front_src[:out_cap].reshape(1, out_cap),
                        inv_which.reshape(1), inv_slot.reshape(1),
                        dead_local.reshape(1), dead_slot.reshape(1),
                        assert_bad.reshape(1), asrt_a.reshape(1),
                        asrt_f.reshape(1), tot_spill.reshape(1))
            out = (seen2.reshape(1, SC, K), seen_count2.reshape(1),
                   front_rows.reshape(1, R, PW), front_count.reshape(1),
                   front_src.reshape(1, R),
                   tot_gen.reshape(1), tot_new.reshape(1),
                   dead_local.reshape(1), dead_slot.reshape(1),
                   assert_bad.reshape(1), asrt_a.reshape(1),
                   asrt_f.reshape(1), any_ovf.reshape(1),
                   inv_which.reshape(1), inv_slot.reshape(1),
                   tot_front.reshape(1), any_a2a_ovf.reshape(1),
                   tot_spill.reshape(1))
            if need_edges:
                # every exchanged candidate row + its explore mask +
                # global source index — the host-side edge stream.
                # gather mode: identical on every device (host reads
                # device 0); a2a: each device holds its own bucket.
                # `evalid` is the PRE-ownership validity from the
                # route: gkeys is already masked to owner-local rows,
                # and recomputing validity from it would silently drop
                # foreign-owned edges from the device-0 read
                # (review r8).
                exp_all = evalid
                gcand_u = plan.unpack_rows(gcand)
                for nm, f in con_fns:
                    exp_all = exp_all & jax.vmap(f)(gcand_u)
                out = out + (gcand.reshape(1, R, PW),
                             exp_all.reshape(1, R),
                             gsrc.reshape(1, R))
            return out

        n_out = 21 if out_cap is not None else \
            (21 if need_edges else 18)
        step = obs.prof_wrap("mesh.level_step", jax.jit(shard_map(
            device_step, mesh=self.mesh,
            in_specs=(P("d"), P("d"), P("d"), P("d")),
            out_specs=tuple([P("d")] * n_out))), key=key)
        self._mesh_step_cache[key] = step
        return step

    def _mk_level_tail(self, SC: int, FC: int, TRL: int, N: int,
                       route: Callable, merge_fn: Callable,
                       with_trace: bool) -> Callable:
        """Everything a resident level does AFTER expansion — route,
        merge-dedup, invariant scan, capacity verdicts, commit-or-
        rollback, trace-ring append, the scalar/aux vectors and the
        stop verdict — as one closure shared by the fused resident
        superstep and the grouped-expansion step (ISSUE 11), so the
        two cannot drift.  Runs inside a shard_map'd device function;
        expansion hands it the candidate block plus the per-device
        fault scalars."""

        por_plan = self._por_plan() if self.por else None
        if por_plan is not None:
            por_inst = jnp.asarray(por_plan["inst_arm"])
            por_safe_v = jnp.asarray(por_plan["arm_safe"])
        A, D, K, C = self.A, self.D, self.K, self.A * FC

        def tail(seen_keys, seen_count, frontier_p, fcount,
                 tr_rows, tr_src, lvl, dist, max_states, me,
                 ckeys, cand, cvalid, gen_local, overflow,
                 dead_local, dead_slot, assert_bad, asrt_a, asrt_f):
            # ---- device POR (ISSUE 18): the ample mask runs BEFORE the
            # exchange, against the PRE-LEVEL seen snapshot — the same
            # rule as the single-chip level/resident engines, so reduced
            # counts are bit-identical across engine shapes.  Every key
            # lives in exactly ONE owner shard: gather all devices'
            # candidate keys, probe the LOCAL shard, psum the verdicts —
            # global membership with no host round-trip, and masked rows
            # never enter the a2a/gather exchange (they also shrink the
            # ICI traffic the reduction is meant to save).
            pora = porx = porm = jnp.int32(0)
            if por_plan is not None:
                with jax.named_scope("jaxmc.mesh.exchange"):
                    allk = lax.all_gather(ckeys, "d")     # [D, C, K]
                fl, _ = _seen_probe(seen_keys, seen_count,
                                    allk.reshape(D * C, K), SC)
                with jax.named_scope("jaxmc.mesh.exchange"):
                    fg = lax.psum(fl.astype(jnp.int32),
                                  "d").reshape(D, C)
                    found = lax.dynamic_slice_in_dim(
                        fg, me, 1, 0)[0] > 0
                keep, pora, porx = _por_mask(
                    found, cvalid, por_inst, por_safe_v, A, FC)
                porm = jnp.sum(cvalid & ~keep, dtype=jnp.int32)
                inv_key = jnp.concatenate([
                    jnp.ones((C, 1), jnp.int32),
                    jnp.full((C, K - 1), SENTINEL, jnp.int32)], axis=1)
                ckeys = jnp.where(keep[:, None], ckeys, inv_key)
                cand = jnp.where(keep[:, None], cand, SENTINEL)
                cvalid = keep
                gen_local = gen_local - porm

            (gkeys, gcand, gsrc, spill_local, a2a_ovf, maxdest,
             _evalid) = route(ckeys, cand, cvalid, me)

            mg = merge_fn(seen_keys, seen_count, gkeys, gcand, gsrc)
            front_rows = mg["front_rows"]
            front_count = mg["front_count"]
            front_src = mg["front_src"]
            seen_count2 = mg["seen_count2"]
            inv_which, inv_slot = self._inv_scan(mg["front_rows_u"],
                                                 front_count, N)

            # ---- capacity verdicts (replicated) ----
            with jax.named_scope("jaxmc.mesh.scalars"):
                f_ovf = lax.psum((front_count > FC).astype(jnp.int32),
                                 "d") > 0
                s_ovf = lax.psum((seen_count2 > SC).astype(jnp.int32),
                                 "d") > 0
                t_ovf = (jnp.asarray(with_trace) & (lvl >= TRL)) \
                    if with_trace else jnp.asarray(False)
                any_a2a_ovf = lax.psum(a2a_ovf.astype(jnp.int32),
                                       "d") > 0
                v_ovf = lax.psum(mg["v_ovf"].astype(jnp.int32),
                                 "d") > 0
                grow = f_ovf | s_ovf | t_ovf | any_a2a_ovf | v_ovf
                commit = ~grow

            # ---- commit or roll back the device state ----
            # (the one table-sized pass a level outside the merge's
            # bounded loops: the roll-back keeps the old table)
            seen_out = jnp.where(commit, mg["seen2"], seen_keys)
            seen_count_out = jnp.where(commit, seen_count2,
                                       seen_count)
            new_frontier = front_rows[:FC]  # N >= FC (VC clamp /
            #                                 a2a floors)
            # ring src rows keep the documented -1-means-empty
            # convention: slots past front_count hold compaction
            # leftovers (nonnegative), and an unmasked write would
            # make _ring_levels' occupied-prefix trim inert
            # (review r8)
            new_src_fc = jnp.where(
                jnp.arange(FC) < front_count,
                front_src[:FC], -1).astype(jnp.int32)
            frontier_out = jnp.where(commit, new_frontier,
                                     frontier_p)
            fcount_out = jnp.where(commit, front_count, fcount)
            if with_trace:
                wl = jnp.clip(lvl, 0, TRL - 1)
                tr_rows2 = lax.dynamic_update_slice(
                    tr_rows, new_frontier[None], (wl, 0, 0))
                tr_src2 = lax.dynamic_update_slice(
                    tr_src, new_src_fc[None], (wl, 0))
                tr_rows_out = jnp.where(commit, tr_rows2, tr_rows)
                tr_src_out = jnp.where(commit, tr_src2, tr_src)
            else:
                tr_rows_out = tr_src_out = None

            # ---- the per-level scalar vector (replicated) ----
            with jax.named_scope("jaxmc.mesh.scalars"):
                tot_new = lax.psum(front_count, "d")
                ovc = lax.pmax(overflow, "d")
                tot_dead = lax.psum(dead_local.astype(jnp.int32), "d")
                tot_assert = lax.psum(
                    assert_bad.astype(jnp.int32), "d")
                inv_min = lax.pmin(inv_which, "d")
                scal = jnp.zeros((_NS,), jnp.int32)
                scal = scal.at[_S_GEN].set(
                    lax.psum(gen_local, "d"))
                scal = scal.at[_S_NEW].set(tot_new)
                scal = scal.at[_S_FRONT].set(tot_new)
                scal = scal.at[_S_MAXF].set(lax.pmax(front_count, "d"))
                scal = scal.at[_S_MAXS].set(lax.pmax(seen_count2, "d"))
                scal = scal.at[_S_SUMS].set(lax.psum(seen_count2, "d"))
                scal = scal.at[_S_OVC].set(ovc)
                scal = scal.at[_S_DEAD].set(tot_dead)
                scal = scal.at[_S_ASSERT].set(tot_assert)
                scal = scal.at[_S_INVMIN].set(inv_min)
                scal = scal.at[_S_FOVF].set(f_ovf.astype(jnp.int32))
                scal = scal.at[_S_SOVF].set(s_ovf.astype(jnp.int32))
                scal = scal.at[_S_TOVF].set(t_ovf.astype(jnp.int32))
                scal = scal.at[_S_AOVF].set(
                    any_a2a_ovf.astype(jnp.int32))
                scal = scal.at[_S_SPILL].set(
                    lax.psum(spill_local, "d"))
                scal = scal.at[_S_MAXDEST].set(lax.pmax(maxdest, "d"))
                scal = scal.at[_S_VOVF].set(v_ovf.astype(jnp.int32))
                scal = scal.at[_S_MAXV].set(
                    lax.pmax(mg["v_need"], "d"))
                scal = scal.at[_S_PORA].set(lax.psum(pora, "d"))
                scal = scal.at[_S_PORX].set(lax.psum(porx, "d"))
                scal = scal.at[_S_PORM].set(lax.psum(porm, "d"))
                scal = scal.at[_S_PROBED].set(
                    lax.psum(mg["probe_blocks"], "d"))
                scal = scal.at[_S_MERGED].set(
                    lax.psum(mg["merge_blocks"], "d"))

                # per-device localization vector (fetched only on
                # violation — always the LAST executed level's, because
                # every violation stops the superstep)
                aux = jnp.zeros((_NA,), jnp.int32)
                aux = aux.at[_A_INVW].set(inv_which)
                aux = aux.at[_A_INVSLOT].set(inv_slot)
                aux = aux.at[_A_DEAD].set(dead_local.astype(jnp.int32))
                aux = aux.at[_A_DEADSLOT].set(dead_slot)
                aux = aux.at[_A_ASSERT].set(
                    assert_bad.astype(jnp.int32))
                aux = aux.at[_A_ASRTA].set(asrt_a)
                aux = aux.at[_A_ASRTF].set(asrt_f)

                # ---- superstep exit verdict (replicated) ----
                dist2 = jnp.where(commit, dist + tot_new, dist)
                viol = (inv_min != _BIG) | (tot_dead > 0) | \
                    (tot_assert > 0) | (ovc != 0)
                trunc = commit & (max_states > 0) & \
                    (dist2 >= max_states)
                done = commit & (tot_new == 0)
                stop = grow | viol | trunc | done
                lvl2 = jnp.where(commit, lvl + 1, lvl)
            return (seen_out, seen_count_out, frontier_out,
                    fcount_out, tr_rows_out, tr_src_out, lvl2,
                    dist2, scal, aux, stop)

        return tail

    def _mesh_resident_key(self, SC: int, FC: int, TRL: int, VC: int):
        """The resident step's compile-cache key — shared with the run
        loop's fresh_compile detection so the two can never disagree."""
        C = self.A * FC
        B = self._a2a_bucket(C, FC) if self.exchange == "a2a" else 0
        SB = self._a2a_spill_bucket(B) if B else 0
        return ("grp" if self._grouped else "res", SC, FC, TRL, B, SB,
                self.store_trace, VC)

    def _get_mesh_resident_step(self, SC: int, FC: int, TRL: int,
                                VC: int) -> Callable:
        """The MESH-RESIDENT superstep (ISSUE 8 tentpole, ISSUE 10
        multi-level fusion): one jitted shard_map dispatch that runs UP
        TO `maxlvl` levels in a lax.while_loop — each level expands,
        exchanges, merge-dedups against the seen shards and appends the
        per-level trace ring IN PLACE — and returns the full device
        state plus a device-side RING of per-level scalar vectors the
        host drains once per superstep (the only thing it reads on the
        clean path).  The loop exits early on violation / deadlock /
        assert / kernel overflow / truncation / empty frontier, and on
        any capacity overflow (seen / frontier / trace ring / a2a
        bucket+spill) the offending level rolls back inside the step
        (its outputs == its inputs), so rollback, violation
        localization, drain and checkpointing keep their exact
        one-level-per-dispatch semantics.

        maxlvl, the level budget per dispatch, is a TRACED argument
        (like the single-chip resident maxlvl) so the host adapts it
        without recompiling.

        Many-instance models on XLA:CPU (self._grouped) get the
        GROUPED-expansion variant instead: same signature, same
        outputs, expansion split into arm-group dispatches (ISSUE
        11)."""
        if self._grouped:
            return self._get_mesh_grouped_step(SC, FC, TRL, VC)
        C = self.A * FC
        route, R, B, SB = self._route_fn(C, FC)
        with_trace = self.store_trace
        key = self._mesh_resident_key(SC, FC, TRL, VC)
        if key in self._mesh_step_cache:
            return self._mesh_step_cache[key]
        K, D, PW = self.K, self.D, self.PW
        plan = self.plan
        block_fn = self._candidate_block_fn(FC)
        merge_fn = self._merge_rank_fn(SC, R, VC, B, SB)
        # N: the merge's compacted output block — the shapes every
        # post-merge consumer (inv scan, frontier crop) runs at
        N = self._merge_out_rows(R, VC)
        check_deadlock = self.model.check_deadlock

        def device_step(seen_keys, seen_count, frontier_p, fcount,
                        *rest):
            if with_trace:
                tr_rows = rest[0].reshape(TRL, FC, PW)
                tr_src = rest[1].reshape(TRL, FC)
                lvl0, maxlvl, dist0, max_states = rest[2:]
            else:
                tr_rows = tr_src = None
                lvl0, maxlvl, dist0, max_states = rest
            seen_keys = seen_keys.reshape(SC, K)
            frontier_p = frontier_p.reshape(FC, PW)
            seen_count0 = seen_count[0]
            fcount0 = fcount[0]
            me = lax.axis_index("d")

            tail = self._mk_level_tail(SC, FC, TRL, N, route, merge_fn,
                                       with_trace)

            def one_level(seen_keys, seen_count, frontier_p, fcount,
                          tr_rows, tr_src, lvl, dist):
                """One BFS level (the PR-8 step body): returns the
                committed-or-rolled-back state, the level's scalar
                vector, the localization vector, and the replicated
                stop verdict.  Expansion here, everything after it in
                the SHARED level tail (_mk_level_tail — the grouped
                expansion step runs the same tail, so the two step
                shapes cannot drift)."""
                with jax.named_scope("jaxmc.expand"):
                    frontier = plan.unpack_rows(frontier_p)
                    fvalid = jnp.arange(FC) < fcount
                    blk = block_fn(frontier, fvalid)
                    dead_local = (jnp.any(blk["dead"])
                                  if check_deadlock
                                  else jnp.asarray(False))
                return tail(seen_keys, seen_count, frontier_p, fcount,
                            tr_rows, tr_src, lvl, dist, max_states, me,
                            blk["ckeys"], blk["cand"], blk["cvalid"],
                            blk["gen_local"], blk["overflow"],
                            dead_local, blk["dead_slot"],
                            blk["assert_bad"], blk["asrt_a"],
                            blk["asrt_f"])

            ring0 = jnp.zeros((_SS_RINGCAP, _NS), jnp.int32)
            aux0 = jnp.zeros((_NA,), jnp.int32)

            # one body serves both trace configurations: without
            # tracing the two trace-ring carry slots hold scalar
            # dummies that thread through unchanged (while_loop
            # carries need consistent pytrees; one_level never
            # touches its tr args when with_trace is False)
            def body(carry):
                (sk, sc_, fp, fc_, trr, trs, lvl, dist, nlv, ring,
                 aux, stop) = carry
                (sk, sc_, fp, fc_, trr2, trs2, lvl, dist, scal,
                 aux, stop) = one_level(
                    sk, sc_, fp, fc_,
                    trr if with_trace else None,
                    trs if with_trace else None, lvl, dist)
                if with_trace:
                    trr, trs = trr2, trs2
                with jax.named_scope("jaxmc.mesh.scalars"):
                    ring = lax.dynamic_update_slice(
                        ring, scal[None], (nlv, 0))
                return (sk, sc_, fp, fc_, trr, trs, lvl, dist,
                        nlv + 1, ring, aux, stop)

            def cond(carry):
                nlv, stop = carry[8], carry[11]
                return (~stop) & (nlv < jnp.minimum(
                    maxlvl, jnp.int32(_SS_RINGCAP)))

            dummy = jnp.int32(0)
            carry0 = (seen_keys, seen_count0, frontier_p, fcount0,
                      tr_rows if with_trace else dummy,
                      tr_src if with_trace else dummy,
                      lvl0, dist0, jnp.int32(0), ring0, aux0,
                      jnp.asarray(False))
            carry = lax.while_loop(cond, body, carry0)
            (seen_f, seen_count_f, frontier_f, fcount_f) = carry[:4]
            tr_rows_f, tr_src_f = (carry[4], carry[5]) \
                if with_trace else (None, None)
            nlv_f, ring_f, aux_f = carry[8], carry[9], carry[10]

            outs = [seen_f.reshape(1, SC, K),
                    seen_count_f.reshape(1),
                    frontier_f.reshape(1, FC, PW),
                    fcount_f.reshape(1)]
            if with_trace:
                outs.append(tr_rows_f.reshape(1, TRL, FC, PW))
                outs.append(tr_src_f.reshape(1, TRL, FC))
            outs.append(ring_f.reshape(1, _SS_RINGCAP, _NS))
            outs.append(nlv_f.reshape(1))
            outs.append(aux_f.reshape(1, _NA))
            return tuple(outs)

        n_in = 10 if with_trace else 8
        n_out = 9 if with_trace else 7
        in_specs = tuple([P("d")] * (n_in - 4)) + (P(), P(), P(), P())
        # donate the big device buffers — seen, frontier, trace ring —
        # so XLA updates them in place across levels (accelerators;
        # XLA:CPU ignores donation with a warning, JAXMC_DONATE forces)
        donate = ((0, 2, 4, 5) if with_trace else (0, 2)) \
            if self.donate else ()
        # check_vma=False: shard_map's replication checker has no rule
        # for lax.while_loop (the superstep level loop); every output
        # is P("d")-sharded anyway, so nothing relied on inferred
        # replication
        step = obs.prof_wrap("mesh.superstep", jax.jit(shard_map(
            device_step, mesh=self.mesh,
            in_specs=in_specs,
            out_specs=tuple([P("d")] * n_out),
            check_vma=False),
            donate_argnums=donate), key=key)
        self._mesh_step_cache[key] = step
        return step

    def _mesh_expand_group_jits(self, FC: int):
        """The arm-group expansion dispatches for the grouped mesh
        level (ISSUE 11): contiguous groups of compiled actions, each
        holding at most fused_max kernel INSTANCES (one slotted kernel
        counts its slot fan-out, exactly like bfs._hstep_groups), each
        group one shard_map'd jit over the mesh.  Returns (jits,
        offsets): offsets[g] is group g's first flat instance index —
        concatenating group candidate blocks in order reproduces the
        fused expansion's [A*FC] candidate order bit-for-bit."""
        ckey = ("grpexp", FC)
        if ckey in self._mesh_step_cache:
            return self._mesh_step_cache[ckey]
        K, PW, W = self.K, self.PW, self.W
        plan = self.plan
        keys_of = self._keys_of
        fused_max = self._mesh_fused_max
        # independence-driven group plan (ISSUE 15) shared with the
        # bfs host_seen path; inst_blocks carry each group's original
        # flat instance indices so the caller can restore provenance
        # order after the group dispatches
        gplan = self._arm_group_plan(fused_max)
        groups = [[self.compiled[i] for i in g] for g in gplan]
        inst_blocks = self._group_inst_blocks(gplan)

        def _mk(subset):
            ag = sum(max(1, ca.n_slots) for ca in subset)
            Cg = ag * FC

            def gdev(frontier_p, fcount):
                frontier = plan.unpack_rows(frontier_p.reshape(FC, PW))
                fvalid = jnp.arange(FC) < fcount[0]
                ens, aoks, ovs, succs = [], [], [], []
                for ca in subset:
                    if ca.n_slots:
                        slots = jnp.arange(ca.n_slots, dtype=jnp.int32)
                        en, aok, ov, succ = jax.vmap(
                            jax.vmap(ca.fn, in_axes=(0, None)),
                            in_axes=(None, 0))(frontier, slots)
                        for si in range(ca.n_slots):
                            ens.append(en[si])
                            aoks.append(aok[si])
                            ovs.append(ov[si])
                            succs.append(succ[si])
                    else:
                        en, aok, ov, succ = jax.vmap(ca.fn)(frontier)
                        ens.append(en)
                        aoks.append(aok)
                        ovs.append(ov)
                        succs.append(succ)
                en = jnp.stack(ens)            # [ag, FC]
                aok = jnp.stack(aoks)
                ov = jnp.stack(ovs)
                succ = jnp.stack(succs)        # [ag, FC, W]
                valid = en & fvalid[None, :]
                abad = (~aok) & fvalid[None, :]
                ov_g = jnp.max(jnp.where(fvalid[None, :], ov, 0)) \
                    .astype(jnp.int32)
                gen_g = jnp.sum(valid)
                cand_u = succ.reshape(Cg, W)
                cvalid = valid.reshape(Cg)
                cand_u = jnp.where(cvalid[:, None], cand_u, SENTINEL)
                ckeys, cand, pack_ovf = keys_of(cand_u, cvalid)
                return (ckeys.reshape(1, Cg, K),
                        cand.reshape(1, Cg, PW),
                        cvalid.reshape(1, Cg),
                        jnp.any(en, axis=0).reshape(1, FC),
                        gen_g.reshape(1), ov_g.reshape(1),
                        jnp.any(abad).reshape(1),
                        jnp.argmax(abad.reshape(-1))
                        .astype(jnp.int32).reshape(1),
                        pack_ovf.reshape(1))

            return obs.prof_wrap("mesh.group_expand", jax.jit(shard_map(
                gdev, mesh=self.mesh, in_specs=(P("d"), P("d")),
                out_specs=tuple([P("d")] * 9))))

        jits = [_mk(g) for g in groups]
        obs.current().gauge("mesh.grouped_expand", len(jits))
        out = (jits, inst_blocks)
        self._mesh_step_cache[ckey] = out
        return out

    def _get_mesh_grouped_step(self, SC: int, FC: int, TRL: int,
                               VC: int) -> Callable:
        """The grouped-expansion resident level (ISSUE 11): expansion
        as ceil(A/fused_max) group dispatches (host-combined fault
        scalars, numpy), then ONE merge/tail dispatch running the
        SHARED level tail — same call signature and output surface as
        the fused resident step, so the run loop cannot tell them
        apart.  Always one level per dispatch (self._ss_fixed == 1).
        No buffer donation: the frontier feeds every group dispatch
        AND the tail, and this path is XLA:CPU-gated anyway."""
        C = self.A * FC
        route, R, B, SB = self._route_fn(C, FC)
        with_trace = self.store_trace
        key = self._mesh_resident_key(SC, FC, TRL, VC)
        if key in self._mesh_step_cache:
            return self._mesh_step_cache[key]
        K, D, PW = self.K, self.D, self.PW
        merge_fn = self._merge_rank_fn(SC, R, VC, B, SB)
        N = self._merge_out_rows(R, VC)
        check_deadlock = self.model.check_deadlock
        tail = self._mk_level_tail(SC, FC, TRL, N, route, merge_fn,
                                   with_trace)
        jits, inst_blocks = self._mesh_expand_group_jits(FC)
        # provenance restore (ISSUE 15): regrouped dispatches emit
        # candidates in group order; one gather puts them back into
        # original instance order so counts/traces stay byte-identical
        inst_order = np.concatenate(inst_blocks) if inst_blocks \
            else np.zeros(0, np.int64)
        identity_order = bool(
            (inst_order == np.arange(self.A)).all())
        pos = np.empty(self.A, np.int64)
        pos[inst_order] = np.arange(self.A)
        cand_perm = (pos[:, None] * FC
                     + np.arange(FC)[None, :]).reshape(-1)
        max_ag = max((len(b) for b in inst_blocks), default=1)
        inst_pad = np.zeros((max(len(inst_blocks), 1), max_ag),
                            np.int64)
        for _gi, _b in enumerate(inst_blocks):
            inst_pad[_gi, :len(_b)] = _b

        def tail_dev(seen_keys, seen_count, frontier_p, fcount, *rest):
            if with_trace:
                tr_rows = rest[0].reshape(TRL, FC, PW)
                tr_src = rest[1].reshape(TRL, FC)
                rest = rest[2:]
            else:
                tr_rows = tr_src = None
            (ckeys, cand, cvalid, gen_local, ov_local, dead_local,
             dead_slot, assert_local, asrt_a, asrt_f, lvl, dist,
             max_states) = rest
            me = lax.axis_index("d")
            (seen_f, seen_count_f, frontier_f, fcount_f, trr, trs,
             _lvl2, _dist2, scal, aux, _stop) = tail(
                seen_keys.reshape(SC, K), seen_count[0],
                frontier_p.reshape(FC, PW), fcount[0],
                tr_rows, tr_src, lvl, dist, max_states, me,
                ckeys.reshape(C, K), cand.reshape(C, PW),
                cvalid.reshape(C), gen_local[0], ov_local[0],
                dead_local[0], dead_slot[0], assert_local[0],
                asrt_a[0], asrt_f[0])
            ring = lax.dynamic_update_slice(
                jnp.zeros((_SS_RINGCAP, _NS), jnp.int32),
                scal[None], (0, 0))
            outs = [seen_f.reshape(1, SC, K), seen_count_f.reshape(1),
                    frontier_f.reshape(1, FC, PW), fcount_f.reshape(1)]
            if with_trace:
                outs.append(trr.reshape(1, TRL, FC, PW))
                outs.append(trs.reshape(1, TRL, FC))
            outs.append(ring.reshape(1, _SS_RINGCAP, _NS))
            outs.append(jnp.ones((1,), jnp.int32))  # nlv: one level
            outs.append(aux.reshape(1, _NA))
            return tuple(outs)

        n_shard = (16 if with_trace else 14)
        n_out = 9 if with_trace else 7
        jtail = obs.prof_wrap("mesh.grouped_tail", jax.jit(shard_map(
            tail_dev, mesh=self.mesh,
            in_specs=tuple([P("d")] * n_shard) + (P(), P(), P()),
            out_specs=tuple([P("d")] * n_out),
            check_vma=False)))

        def step(seen, seen_count, frontier, fcount, *args):
            if with_trace:
                tr = (args[0], args[1])
                lvl0, _maxlvl, dist0, max_states = args[2:]
            else:
                tr = ()
                lvl0, _maxlvl, dist0, max_states = args
            outs = [jf(frontier, fcount) for jf in jits]
            ckeys = jnp.concatenate([o[0] for o in outs], axis=1)
            cand = jnp.concatenate([o[1] for o in outs], axis=1)
            cvalid = jnp.concatenate([o[2] for o in outs], axis=1)
            if not identity_order:
                permj = jnp.asarray(cand_perm, jnp.int32)
                ckeys = jnp.take(ckeys, permj, axis=1)
                cand = jnp.take(cand, permj, axis=1)
                cvalid = jnp.take(cvalid, permj, axis=1)
            # host-combined per-device fault scalars (tiny [D] reads):
            # exactly what the fused step's block_fn computes inline
            en_any = np.logical_or.reduce(
                [np.asarray(o[3]) for o in outs])        # [D, FC]
            gen_local = np.sum([np.asarray(o[4]) for o in outs],
                               axis=0).astype(np.int32)
            ovmax = np.max([np.asarray(o[5]) for o in outs],
                           axis=0).astype(np.int32)
            povf = np.logical_or.reduce(
                [np.asarray(o[8]) != 0 for o in outs])   # pack guard
            ov_local = np.where(
                ovmax != 0, ovmax,
                np.where(povf, OV_PACK, 0)).astype(np.int32)
            fcnt = np.asarray(fcount)
            fvalid = np.arange(FC)[None, :] < fcnt[:, None]
            dead = fvalid & ~en_any
            if check_deadlock:
                dead_local = dead.any(axis=1)
            else:
                dead_local = np.zeros(D, bool)
            dead_slot = dead.argmax(axis=1).astype(np.int32)
            aa = np.stack([np.asarray(o[6]) != 0 for o in outs])
            af = np.stack([np.asarray(o[7]) for o in outs])
            assert_local = aa.any(axis=0)
            # pick the asserting row FIRST IN ORIGINAL instance order
            # (the fused step's argmax semantics) across the groups:
            # per-group first-assert rows map through inst_pad back to
            # original flat indices, then min-reduce
            g_arange = np.arange(aa.shape[0])[:, None]
            orig_flat = inst_pad[g_arange, af // FC] * FC + af % FC
            orig_flat = np.where(aa, orig_flat, np.int64(2 ** 62))
            sel = orig_flat.min(axis=0)                      # [D]
            asrt_a = np.where(assert_local, sel // FC,
                              0).astype(np.int32)
            asrt_f = np.where(assert_local, sel % FC,
                              0).astype(np.int32)
            targs = (seen, seen_count, frontier, fcount) + tr + (
                ckeys, cand, cvalid,
                jnp.asarray(gen_local), jnp.asarray(ov_local),
                jnp.asarray(dead_local), jnp.asarray(dead_slot),
                jnp.asarray(assert_local), jnp.asarray(asrt_a),
                jnp.asarray(asrt_f),
                lvl0, dist0, max_states)
            return jtail(*targs)

        self._mesh_step_cache[key] = step
        return step

    def _init_shards(self, init_rows: np.ndarray, explored_idx,
                     D: int, SC: int, FC: int,
                     keys=None, packed=None, owner=None):
        """Host-side initial shard construction shared by the
        resident seed (`_mesh_seed`: at the size of the fullest shard —
        the HEADS, which the devices extend to capacity with the same
        `_invalid_row`), the host loop and the multi-host loop
        (tpu/multihost.py; both at full capacity): per-owner frontier
        fill and lexsorted seen keys with the validity-lane-1 empty-slot
        convention. One layout rule, so host and device dedup can never
        diverge. Returns (seen [D,SC,K], frontier [D,FC,PW],
        fcount [D], seen_counts [D]) as numpy — the per-shard
        valid-prefix lengths the rank merge keys on, returned here so
        no caller re-derives them from the validity lane."""
        K = self.K
        if keys is None:
            keys, packed, povf = self._host_keys(init_rows)
            if povf:
                from ..compile.vspec import CompileError
                raise CompileError(self._pack_ovf_msg())
            owner = self._owner_from_keys(keys)
        tel = obs.current()  # seed.tables_s / .keys_s: _mesh_seed
        with tel.timed("seed.tables_s"):
            exp = np.zeros(len(init_rows), bool)
            exp[np.asarray(explored_idx, int)] = True
            frontier = np.full((D, FC, self.PW), SENTINEL, np.int32)
            seen = np.empty((D, SC, K), np.int32)
            seen[:] = _invalid_row(K)  # empty slots: validity lane 1
            fcount = np.zeros((D,), np.int32)
            seen_counts = np.zeros((D,), np.int32)
        for d in range(D):
            with tel.timed("seed.tables_s"):
                p = packed[(owner == d) & exp]
                frontier[d, :len(p)] = p
                k = keys[owner == d]
            if len(k):
                with tel.timed("seed.keys_s"):
                    order = np.lexsort(tuple(k[:, i]
                                             for i in reversed(range(K))))
                with tel.timed("seed.tables_s"):
                    seen[d, :len(k)] = k[order]
            fcount[d] = len(p)
            seen_counts[d] = len(k)
        return seen, frontier, fcount, seen_counts

    # ---- trace reconstruction (host side) ----
    #
    # self._levels[L] = (rows [D, cap_L, W] np, src [D, cap_L] np | None).
    # Level 0 holds the initial frontier (src None). For L >= 1, slot i on
    # device d holds global candidate index g = src[d][i]; with C_L =
    # A * FC_L (the expanding level's capacity): source device g // C_L,
    # candidate c = g % C_L, action c // FC_L, parent slot c % FC_L.
    # The resident loop materializes _levels lazily from the device
    # trace ring (one pull, only on a violation or checkpoint).

    def _mesh_trace_to(self, dev: int, slot: int, depth: int,
                       extra: Optional[Tuple[Dict, str]] = None):
        if not self.store_trace:
            return None
        out = []
        d, i = dev, slot
        for lvl in range(depth, -1, -1):
            rows, src, FC = self._levels[lvl]
            st = self.layout.decode_packed(np.asarray(rows[d][i]))
            if lvl == 0:
                out.append((st, "Initial predicate"))
            else:
                g = int(src[d][i])
                C = self.A * FC
                a = (g % C) // FC
                out.append((st, self.labels_flat[a]))
                d, i = g // C, (g % C) % FC
        out.reverse()
        if extra is not None:
            out.append(extra)
        return out

    def _mesh_refine_edges(self, frontier_np, ecand, eexp, esrc,
                           FC, depth):
        """Stepwise refinement over this level's explored candidate
        edges — the host runs the SAME checkers as the single-chip
        modes, with parents resolved through the global source index
        (g -> source device, action, frontier slot)."""
        C = self.A * FC
        idxs = np.nonzero(eexp)[0]
        if not len(idxs):
            return None
        parents: Dict[Tuple[int, int], dict] = {}
        if len(self._ref_pair_cache) > (1 << 20):
            self._ref_pair_cache.clear()
        for c in idxs:
            g = int(esrc[c])
            d_src, cc = g // C, g % C
            a, f = cc // FC, cc % FC
            key = (frontier_np[d_src, f].tobytes(), ecand[c].tobytes())
            if key in self._ref_pair_cache:
                continue
            self._ref_pair_cache.add(key)
            pst = parents.get((d_src, f))
            if pst is None:
                pst = self.layout.decode_packed(frontier_np[d_src, f])
                parents[(d_src, f)] = pst
            sst = self.layout.decode_packed(ecand[c])
            for rc in self.refiners:
                if not rc.check_edge(pst, sst):
                    trace = self._mesh_trace_to(
                        d_src, f, depth,
                        extra=(sst, self.labels_flat[a]))
                    return self._viol("property", rc.name, trace,
                                      self._refine_msg(rc))
        return None

    def _viol(self, kind, name, trace, msg=None):
        if trace is None:
            note = (f"{kind} found (mesh traces disabled by "
                    f"store_trace=False)")
            return Violation(kind, name, [], msg or note)
        return Violation(kind, name, trace, msg)

    # ---- checkpoint/resume (level boundaries) ----

    def _mesh_ck(self, seen, seen_counts, frontier, fcount, FC, SC,
                 depth, generated, distinct):
        self._write_ck(
            "mesh", D=self.D, FC=FC, SC=SC, depth=depth,
            generated=generated, distinct=distinct,
            seen=np.asarray(seen), seen_counts=np.asarray(seen_counts),
            frontier=np.asarray(frontier), fcount=np.asarray(fcount),
            levels=self._levels if self.store_trace else None)

    def run(self) -> CheckResult:
        # the edge stream feeds refiners and non-[]P liveness; []P-only
        # obligations still need the behavior-graph STATES (per-level
        # kept rows), so the mode guards key on the wider condition
        need_edges = bool(self.refiners) or self.collect_edges
        need_props = bool(self.refiners) or bool(self.live_obligations)
        self._begin_search()
        # per-RUN accounting: the final gauges (_mk) must describe THIS
        # run — a warm re-run (bench timed window) must not inherit the
        # warm-up's spill/bucket peaks (review r8).  Learned caps and
        # gamma deliberately persist on the instance.
        self._spill_rows = 0
        self._max_bucket = 0
        self._shard_balance = None
        self._supersteps = 0
        self._superstep_levels_max = 0
        self._ss_shrunk = False
        # chosen strategy + gamma, once per run (ISSUE 8 satellite)
        resident = not (need_props or need_edges or
                        os.environ.get("JAXMC_MESH_RESIDENT", "1")
                        == "0")
        self.log(f"-- mesh: {self.D} device(s), exchange="
                 f"{self.exchange} ({self._exchange_src}), "
                 f"gamma={self._a2a_gamma:g}, "
                 f"loop={'resident' if resident else 'host'}"
                 + (" [mesh_skew fault armed]" if self._skew else ""))
        tel = obs.current()
        tel.gauge("mesh.exchange", self.exchange)
        # which form the merge's two compactions take (ISSUE 33); the
        # host loop's legacy step compacts no valid candidates
        if resident:
            tel.gauge("mesh.compact_form", self._compact_form)
        tel.gauge("mesh.finish_form", self._finish_form)
        tel.gauge("mesh.devices", self.D)
        # the mesh engine's own dedup stamp (ISSUE 10 satellite):
        # TpuExplorer.__init__ gauges dedup.mode BEFORE the mesh
        # subclass forces fp128 keys, so multichip artifacts carried a
        # stale (or, under serve/bench telemetry scoping, no) value —
        # re-stamp it here so `obs report` highlights name the dedup
        # mode that actually ran
        tel.gauge("dedup.mode",
                  "fp128" + ("-view" if self.view_fn is not None
                             else ("-packed" if not self.plan.identity
                                   else "")))
        # likewise seen.mode (ISSUE 12): the base constructor stamped
        # it before the mesh subclass forced fp128 keys
        tel.gauge("seen.mode", "fingerprint")
        if resident:
            return self._run_mesh_resident()
        if self.seen_cap is not None:
            # the legacy host loop (refinement/temporal PROPERTYs)
            # keeps the historical grow-forever behavior: name it
            # instead of silently ignoring the cap
            self.log("-- mesh host loop: --seen-cap/JAXMC_SEEN_CAP is "
                     "ignored here (tier spill runs on the resident "
                     "mesh loop; refinement/temporal PROPERTYs force "
                     "the host loop)")
        return self._run_hostloop(need_edges, need_props)

    # ------------------------------------------------------------------
    # the MESH-RESIDENT loop (ISSUE 8 tentpole)
    # ------------------------------------------------------------------

    def _put(self, host_arr):
        """Place a [D, ...] host array on the mesh SHARDED over its
        leading axis — each device receives only its own shard.  A
        plain jnp.asarray lands all D shards on device 0 and leaves the
        first shard_map dispatch to reshard them: D x a shard's memory
        on one chip at every (re)seed and capacity regrowth, and a
        buffer that changes sharding cannot be donated."""
        return jax.device_put(np.asarray(host_arr), self._shard0)

    def _pad_dev(self, arr, axis: int, newdim: int, fill: int,
                 lane1: bool = False):
        """Grow a [D, ...] device array along `axis` with constant fill
        (validity-lane-1 empty-slot convention for seen shards)."""
        shape = list(arr.shape)
        shape[axis] = newdim - shape[axis]
        pad = np.full(shape, fill, np.int32)
        if lane1:
            pad[..., 0] = 1
        return jnp.concatenate([arr, self._put(pad)], axis=axis)

    def _ring_levels(self, tr_rows, tr_src, upto: int) -> None:
        """Materialize self._levels[1..upto] from the device trace ring
        — the ONE row pull a violating/checkpointing resident run pays
        (mesh.row_syncs)."""
        if not self.store_trace or upto <= 0:
            return
        tel = obs.current()
        tel.counter("mesh.row_syncs")
        rows_np = np.asarray(tr_rows)   # [D, TRL, FC, PW]
        src_np = np.asarray(tr_src)     # [D, TRL, FC]
        del self._levels[1:]
        for l in range(upto):
            # trim to the occupied prefix (src == -1 marks empty slots)
            occ = np.nonzero((src_np[:, l] >= 0).any(axis=0))[0]
            keep = int(occ.max()) + 1 if len(occ) else 1
            self._levels.append((rows_np[:, l, :keep].copy(),
                                 src_np[:, l, :keep].copy(),
                                 self._lvl_FC[l]))

    def _run_mesh_resident(self) -> CheckResult:
        t0 = time.time()
        tel = obs.current()
        warnings = ["mesh backend: dedup on 128-bit fingerprints; "
                    "collision probability < n^2 * 2^-129"]
        warnings.extend(self._temporal_warnings())
        warnings.extend(self._symmetry_warnings())
        warnings.extend(self._por_warnings())

        with tel.span("search.init"):
            init_rows, explored_init, n_init, err = \
                self._prepare_init(t0, warnings)
        if err is not None:
            return err
        explored_mask = np.zeros(n_init, bool)
        explored_mask[explored_init] = True

        self._levels: List[Tuple[np.ndarray, Optional[np.ndarray], int]] \
            = []
        self._lvl_FC = []
        with tel.span("search.seed"):
            seeded = self._mesh_seed(init_rows, explored_mask)
        return self._mesh_supersteps(t0, warnings, *seeded)

    def _mesh_table(self, shape, head=None, fill=SENTINEL):
        """A [D, ...] table made on the mesh from its [D, H, ...] head
        block (`_device_table`): each device fills its own shard, nothing
        lands on device 0, and the sharding is the superstep program's,
        to which the buffer is donated (what `_put` is for)."""
        return self._device_table(shape, head, fill, sharding=self._shard0)

    def _mesh_seed(self, init_rows, explored_mask):
        """Shard construction for one search: the host computes the
        owner-hashed HEADS — the init shards at the size of the fullest
        one, `_init_shards`' layout rule, or a checkpoint's rows — and
        the devices make the capacity-sized shards and the trace ring
        from them (`_mesh_table`).  Nothing is waited for: `search.seed`
        ends when the fills are enqueued.  Returns what
        `_mesh_supersteps` takes."""
        D, K, PW = self.D, self.K, self.PW
        generated = self._init_generated
        distinct = int(explored_mask.sum())
        hint = self._mesh_caps_hint
        # the host's pieces of the seed on the program's own clock
        # (ISSUE 34; bench/SPANS.records.md): `seed.keys_s` keys, owner
        # hash and per-shard order, `seed.tables_s` the host-built
        # head blocks, `seed.upload_s` the calls that hand them over
        # and make the tables, up to their return.  Float counters, not
        # spans: `search.seed` keeps its idle seconds
        tel = obs.current()

        ring_head = src_head = None
        if self.resume_from:
            ck = self._load_ck("mesh")
            if ck["D"] != D:
                raise ValueError(
                    f"cannot resume: checkpoint has {ck['D']} devices, "
                    f"mesh has {D}")
            FC = max(ck["FC"], _pow2_at_least(
                int(hint.get("FC", 1)), lo=64))
            SC = max(ck["SC"], _pow2_at_least(
                int(hint.get("SC", 1)), lo=256))
            depth = ck["depth"]
            generated = ck["generated"]
            distinct = ck["distinct"]
            # the checkpoint's rows are the heads: each table up to its
            # fullest shard's count
            scount_np = ck["seen_counts"].astype(np.int32)
            fcount_np = ck["fcount"].astype(np.int32)
            seen_head = ck["seen"][:, :int(scount_np.max())]
            fr_head = ck["frontier"][:, :int(fcount_np.max())]
            if ck.get("levels") is not None:
                self._levels = list(ck["levels"])
            elif self.store_trace:
                # advisor r3: match _restore_ck_state — a user expecting
                # traces must hear it up front, not get an empty-trace
                # violation later
                raise ValueError(
                    "cannot resume with traces: the checkpoint was "
                    "written with --no-trace")
            self._lvl_FC = [lv[2] for lv in self._levels[1:]]
            TRL = _pow2_at_least(
                max(depth + 1, int(hint.get("TRL", 1)), 16), lo=16)
            if self.store_trace and self._levels[1:]:
                # the ring's head: the checkpoint's levels, each up to
                # its occupied prefix (`_ring_levels` trimmed them)
                with tel.timed("seed.tables_s"):
                    kept = [(rows[:, :FC], src[:, :FC])
                            for rows, src, _fcl in self._levels[1:]]
                    widest = max(rows.shape[1] for rows, _ in kept)
                    ring_head = np.full((D, len(kept), widest, PW),
                                        SENTINEL, np.int32)
                    src_head = np.full((D, len(kept), widest), -1,
                                       np.int32)
                    for l, (rows, src) in enumerate(kept):
                        ring_head[:, l, :rows.shape[1]] = rows
                        src_head[:, l, :src.shape[1]] = src
            self.log(f"Resuming mesh run at depth {depth} "
                     f"({distinct} distinct states)")
        else:
            with tel.timed("seed.keys_s"):
                init_keys, init_packed, init_povf = \
                    self._host_keys(init_rows)
            if init_povf:
                from ..compile.vspec import CompileError
                raise CompileError(self._pack_ovf_msg())
            with tel.timed("seed.keys_s"):
                owner = self._owner_from_keys(init_keys)
                most_keys = int(np.bincount(owner, minlength=D).max())
                most_rows = int(np.bincount(owner[explored_mask],
                                            minlength=D).max())
            FC = _pow2_at_least(
                max(most_rows, 1, int(hint.get("FC", 1))), lo=64)
            SC = _pow2_at_least(max(4 * FC, int(hint.get("SC", 1))),
                                lo=256)
            shard_cap = self._mesh_shard_cap()
            if shard_cap is not None:
                # device seen cap (ISSUE 12): bound each shard's hot
                # tier from the start, floored so every shard seats
                # its init keys (a too-small cap soft-breaches)
                SC = min(SC, shard_cap)
                SC = max(SC, _pow2_at_least(max(most_keys, 1), lo=64))
            TRL = _pow2_at_least(max(int(hint.get("TRL", 1)), 16),
                                 lo=16)
            explored_idx = np.nonzero(explored_mask)[0]
            # the one layout rule, at the size of the fullest shard
            seen_head, fr_head, fcount_np, scount_np = \
                self._init_shards(
                    init_rows, explored_idx, D, most_keys, most_rows,
                    keys=init_keys, packed=init_packed, owner=owner)
            if self.store_trace:
                # level 0 for trace reconstruction: the head rows
                self._levels.append((fr_head, None, FC))
            depth = 0

        with tel.timed("seed.upload_s"):
            seen = self._mesh_table((D, SC, K), seen_head,
                                    fill=_invalid_row(K))
            frontier = self._mesh_table((D, FC, PW), fr_head)
            fcount = self._put(fcount_np)
            seen_count = self._put(scount_np)
            tr_rows = tr_src = None
            if self.store_trace:
                tr_rows = self._mesh_table((D, TRL, FC, PW), ring_head)
                tr_src = self._mesh_table((D, TRL, FC), src_head, fill=-1)
                # _levels beyond the init level will be re-materialized
                # from the ring on demand; keep only level 0 host-side
                del self._levels[1:]
        # the bytes of the heads handed to the devices (ISSUE 35): the
        # capacity-sized tables are made there
        tel.counter("search.seed_bytes", _nbytes(
            seen_head, fr_head, ring_head, src_head))
        return (seen, seen_count, frontier, fcount, tr_rows, tr_src, SC,
                FC, TRL, depth, generated, distinct)

    def _mesh_supersteps(self, t0, warnings, seen, seen_count, frontier,
                         fcount, tr_rows, tr_src, SC: int, FC: int,
                         TRL: int, depth: int, generated: int,
                         distinct: int) -> CheckResult:
        """The host side of the resident loop: one dispatch and one
        scalar-ring drain per superstep, until the frontier is empty
        or a verdict stops the search."""
        tel = obs.current()
        model = self.model
        D, PW = self.D, self.PW
        last_progress = last_ck = time.time()
        lvl_frontier = int(np.sum(np.asarray(fcount)))
        # the shards' seen rows at the last committed level: what a level
        # adds beyond its kept rows a CONSTRAINT discarded (ISSUE 51)
        seen_sum = int(np.sum(np.asarray(seen_count))) \
            if self.constraint_fns else 0
        # rank-merge valid-candidate capacity (ISSUE 11): starts at the
        # learned/heuristic value, grows by rollback-and-redo exactly
        # like SC/FC/TRL when a level's valid exchanged rows outgrow it
        VC = self._initial_vc(FC)
        # superstep controller (ISSUE 10): JAXMC_MESH_SUPERSTEP pins
        # the level budget per dispatch; auto starts at the learned
        # warm value (1 on a cold engine — the first dispatch is
        # exactly the one-level program run) and adapts to measured
        # dispatch wall so progress, checkpoint and drain attention
        # keep their cadence, like the single-chip resident maxlvl
        # controller (backend/bfs.py)
        maxlvl = self._ss_fixed or min(self._mesh_maxlvl_warm,
                                       _SS_RINGCAP)
        target_s = max(1.0, min(
            self.progress_every or 30.0,
            (self.checkpoint_every or 1e9) if self.checkpoint_path
            else 1e9))
        while lvl_frontier > 0:
            lvl_t0 = time.time()
            # chaos sites: crash / drain between dispatches — with
            # supersteps these are SUPERSTEP boundaries, the only
            # host-attention points the resident mesh loop has
            # (jaxmc/faults.py)
            faults.kill_self("run_kill", level=depth, engine="mesh")
            faults.inject("device_run_fail", level=depth, engine="mesh")
            if self._drain_requested(warnings, "mesh"):
                if self.checkpoint_path:
                    self._ring_levels(tr_rows, tr_src, depth)
                    self._mesh_ck(seen, np.asarray(seen_count),
                                  frontier, fcount, FC, SC, depth,
                                  generated, distinct)
                return self._mk(True, distinct, generated, depth, t0,
                                warnings, truncated=True, drained=True)

            C = self.A * FC
            B = self._a2a_bucket(C, FC) if self.exchange == "a2a" else 0
            SB = self._a2a_spill_bucket(B) if B else 0
            step_key = self._mesh_resident_key(SC, FC, TRL, VC)
            fresh_compile = step_key not in self._mesh_step_cache
            step = self._get_mesh_resident_step(SC, FC, TRL, VC)
            args = (seen, seen_count, frontier, fcount)
            if self.store_trace:
                args = args + (tr_rows, tr_src)
            # the tables the superstep carries from level to level at
            # the capacities in force, over all D shards: seen,
            # frontier, the trace ring
            tel.gauge("search.table_bytes", _nbytes(
                seen, frontier, tr_rows, tr_src))
            # once spilled (ISSUE 12) every level needs a cold-tier
            # probe at the host boundary: pin supersteps to one level
            eff_maxlvl = 1 if (self._tiers is not None
                               and self._tiers.active) else maxlvl
            with tel.span("search.dispatch", maxlvl=eff_maxlvl,
                          fresh_compile=fresh_compile):
                args = args + (jnp.int32(depth), jnp.int32(eff_maxlvl),
                               jnp.int32(distinct),
                               jnp.int32(self.max_states or 0))
                outs = step(*args)
                if self.store_trace:
                    (seen2, seen_count2, frontier2, fcount2, tr_rows2,
                     tr_src2, ring_d, nlv_d, aux_d) = outs
                else:
                    (seen2, seen_count2, frontier2, fcount2, ring_d,
                     nlv_d, aux_d) = outs
                    tr_rows2 = tr_src2 = None
                jax.block_until_ready(ring_d)
            # THE one host sync of the superstep: the replicated
            # per-level scalar ring + its occupancy (every per-device
            # row is identical; tiny).  mesh.host_syncs therefore
            # counts SUPERSTEPS, not levels (obs/schema.py PR-10).
            with tel.span("search.fetch"):
                ring = np.asarray(ring_d)[0]
                nlv = max(1, int(np.asarray(nlv_d)[0]))
            disp_wall = time.time() - lvl_t0
            tel.counter("mesh.host_syncs")
            tel.counter("mesh.exchange_bytes",
                        self._exchange_bytes(C, B, SB) * nlv)
            # work against capacity, summed over the shards (PERF.md
            # §3): every level the dispatch ran — a rolled-back one too
            # — sorted the merge's N key slots and rewrote SC seen rows
            # on each of the D shards, whatever was valid
            R = D * (B + SB) if B else D * C  # rows a shard receives
            n_keys = self._merge_out_rows(R, VC)
            tel.gauge("merge.build_form", _build_form(n_keys))
            tel.counter("search.slots_sorted", nlv * D * n_keys)
            if self.constraint_fns:
                # ... and judged every slot of the merge's block of new
                # rows on each shard (ISSUE 51)
                tel.counter("search.slots_constrained", nlv * D * n_keys)
            tel.counter("search.seen_slots", nlv * D * SC)
            # ... and binary-searched only the query blocks that held a
            # valid key on each shard: the ring carries their number a
            # level, summed over the shards
            tel.counter("search.slots_probed",
                        int(ring[:nlv, _S_PROBED].sum())
                        * _probe_block_rows(n_keys))
            # ... and built only the blocks of each shard's table that
            # held a live row after the level
            tel.counter("search.slots_merged",
                        int(ring[:nlv, _S_MERGED].sum())
                        * _merge_block_rows(SC))
            self._supersteps += 1
            self._superstep_levels_max = max(self._superstep_levels_max,
                                             nlv)
            # adopt the device state: levels before a rolled-back or
            # violating level committed inside the dispatch, the
            # offending level itself rolled back (outputs == inputs)
            seen, seen_count = seen2, seen_count2
            frontier, fcount = frontier2, fcount2
            if self.store_trace:
                tr_rows, tr_src = tr_rows2, tr_src2
            # adapt the level budget toward the host-attention target;
            # a dispatch that just paid an XLA recompile is not
            # evidence about execution speed — skip it.  The warm
            # value tracks the SETTLED budget (it follows halvings
            # down), not the running max: a budget the controller
            # judged too slow must not come back on warm runs, where
            # it would stall drain/checkpoint attention for the whole
            # oversized dispatch (review r10)
            if self._ss_fixed is None:
                if fresh_compile:
                    pass
                elif disp_wall > 1.5 * target_s and maxlvl > 1:
                    maxlvl = max(1, maxlvl // 2)
                    self._ss_shrunk = True
                elif disp_wall < target_s / 4 and maxlvl < _SS_RINGCAP:
                    maxlvl = min(_SS_RINGCAP, maxlvl * 2)
                self._mesh_maxlvl_warm = maxlvl
            lwall = round(disp_wall / nlv, 6)

            # ---- drain the ring: one record per executed level, the
            # exact PR-8 one-level host sequence replayed per entry ----
            for li in range(nlv):
                scal = ring[li]
                fresh = fresh_compile and li == 0
                ovc = int(scal[_S_OVC])
                if ovc:
                    if ovc == OV_DEMOTED:
                        msg = ("a demoted compile-recovery fired (the "
                               "kernel under-approximates here): run "
                               "the host_seen mode, which demotes the "
                               "arm to the interpreter and restarts — "
                               "raising caps cannot help")
                    elif ovc == OV_PACK:
                        msg = self._pack_ovf_msg()
                    else:
                        msg = ("a container exceeded its lane capacity "
                               f"({self._caps_note()}); counts would "
                               "no longer be exact")
                    return self._mk(False, distinct, generated, depth,
                                    t0, warnings, Violation(
                                        "error", "capacity overflow",
                                        [], msg))

                if scal[_S_FOVF] or scal[_S_SOVF] or scal[_S_TOVF] or \
                        scal[_S_AOVF] or scal[_S_VOVF]:
                    # the step rolled this level back on device (and
                    # stopped the superstep, so it is the ring's LAST
                    # entry): grow every flagged capacity at once
                    # (each growth recompiles the step, so batching
                    # growths minimizes recompiles), then redo the
                    # level in the next dispatch
                    grew = []
                    if scal[_S_AOVF]:
                        # grow gamma straight to the OBSERVED per-peer
                        # need (the max bucket occupancy rode the
                        # scalar vector) instead of blind doubling:
                        # one rerun covers even pathological skew, and
                        # the spill bucket keeps absorbing
                        # between-level drift afterwards
                        need_g = int(scal[_S_MAXDEST]) * self.D \
                            / max(C, 1)
                        self._a2a_gamma = max(self._a2a_gamma * 2,
                                              need_g)
                        grew.append(f"gamma->{self._a2a_gamma:g}")
                    if scal[_S_SOVF]:
                        SC2 = _pow2_at_least(int(scal[_S_MAXS]),
                                             lo=2 * SC)
                        shard_cap = self._mesh_shard_cap()
                        scounts_now = np.asarray(seen_count)
                        if shard_cap is not None and SC2 > shard_cap \
                                and scounts_now.sum() > 0:
                            # per-shard device tier full (ISSUE 12):
                            # spill every shard's sorted prefix to the
                            # cold tiers and redo the level against
                            # empty shards instead of growing past the
                            # cap
                            seen, seen_count = self._mesh_tier_spill(
                                seen, seen_count, SC)
                            seen_sum = 0
                            # the rolled-back level runs a second time
                            tel.counter("tier.redone_rows",
                                        int(scal[_S_GEN]))
                            grew.append(
                                f"seen->tier-spill("
                                f"{int(scounts_now.sum())} keys, "
                                f"host={self._tiers.host_keys} "
                                f"disk={self._tiers.disk_keys})")
                        else:
                            if shard_cap is not None and SC2 > shard_cap:
                                # nothing left to spill: one level's
                                # keys alone exceed the shard's cap
                                self._note_cap_breach(
                                    SC2 * self.D,
                                    f"one level's keys on a shard "
                                    f"(cap {shard_cap} a shard)")
                            seen = self._pad_dev(seen, 1, SC2, SENTINEL,
                                                 lane1=True)
                            SC = SC2
                            grew.append(f"SC->{SC}")
                    if scal[_S_FOVF]:
                        FC2 = _pow2_at_least(int(scal[_S_MAXF]),
                                             lo=2 * FC)
                        frontier = self._pad_dev(frontier, 1, FC2,
                                                 SENTINEL)
                        if self.store_trace:
                            tr_rows = self._pad_dev(tr_rows, 2, FC2,
                                                    SENTINEL)
                            tr_src = self._pad_dev(tr_src, 2, FC2, -1)
                        FC = FC2
                        grew.append(f"FC->{FC}")
                    if scal[_S_TOVF]:
                        TRL2 = _pow2_at_least(depth + 1, lo=2 * TRL)
                        tr_rows = self._pad_dev(tr_rows, 1, TRL2,
                                                SENTINEL)
                        tr_src = self._pad_dev(tr_src, 1, TRL2, -1)
                        TRL = TRL2
                        grew.append(f"TRL->{TRL}")
                    if scal[_S_VOVF]:
                        # grow straight to the observed valid-row need
                        # (it rode the scalar vector), like gamma —
                        # pure recompile, no device buffers to pad
                        VC = max(FC, _pow2_at_least(
                            int(scal[_S_MAXV]), lo=2 * VC))
                        grew.append(f"VC->{VC}")
                    if scal[_S_FOVF]:
                        # the compacted block must still cover the
                        # frontier crop after FC growth
                        VC = max(VC, FC)
                    self._remember_caps(SC, FC, TRL, VC)
                    self.log(f"-- mesh: growing {', '.join(grew)} "
                             f"(level {depth} redone)")
                    tel.level(depth, frontier=lvl_frontier, generated=0,
                              new=0, distinct=distinct, devices=D,
                              redo=",".join(grew),
                              fresh_compile=fresh,
                              wall_s=lwall)
                    break

                # committed level
                if self.store_trace:
                    self._lvl_FC.append(FC)
                self._spill_rows += int(scal[_S_SPILL])
                self._max_bucket = max(self._max_bucket,
                                       int(scal[_S_MAXDEST]))
                self._vc_seen_need = max(self._vc_seen_need,
                                         int(scal[_S_MAXV]))

                # deadlock/assert live in the CURRENT frontier (depth
                # d): totals exclude the partial level, like the host
                # loop
                if model.check_deadlock and scal[_S_DEAD]:
                    aux = np.asarray(aux_d)
                    dv = int(np.argmax(aux[:, _A_DEAD]))
                    ds = int(aux[dv, _A_DEADSLOT])
                    self._ring_levels(tr_rows, tr_src, depth)
                    trace = self._mesh_trace_to(dv, ds, depth)
                    return self._mk(False, distinct, generated, depth,
                                    t0, warnings,
                                    self._viol("deadlock", "deadlock",
                                               trace))
                if scal[_S_ASSERT]:
                    aux = np.asarray(aux_d)
                    av = int(np.argmax(aux[:, _A_ASSERT]))
                    aa = int(aux[av, _A_ASRTA])
                    af = int(aux[av, _A_ASRTF])
                    self._ring_levels(tr_rows, tr_src, depth)
                    trace = self._mesh_trace_to(av, af, depth)
                    return self._mk(
                        False, distinct, generated, depth, t0,
                        warnings,
                        self._viol("assert", "Assert", trace,
                                   f"assertion in "
                                   f"{self.labels_flat[aa]}"))

                generated += int(scal[_S_GEN])
                distinct += int(scal[_S_NEW])
                tel.counter("search.rows_valid", int(scal[_S_GEN]))
                tel.counter("search.rows_new", int(scal[_S_NEW]))
                self._por_stats["ample"] += int(scal[_S_PORA])
                self._por_stats["expanded"] += int(scal[_S_PORX])
                self._por_stats["masked"] += int(scal[_S_PORM])
                sum_seen = int(scal[_S_SUMS])
                max_seen = int(scal[_S_MAXS])
                if self.constraint_fns:
                    tel.counter("search.rows_discarded",
                                sum_seen - seen_sum - int(scal[_S_NEW]))
                seen_sum = sum_seen
                self._fp_occupancy = sum_seen
                if sum_seen:
                    self._shard_balance = max_seen / (sum_seen / D)
                tel.level(depth, frontier=lvl_frontier,
                          generated=int(scal[_S_GEN]),
                          new=int(scal[_S_NEW]), distinct=distinct,
                          seen=sum_seen, devices=D, fc=FC,
                          spill=int(scal[_S_SPILL]),
                          max_bucket=int(scal[_S_MAXDEST]),
                          superstep=self._supersteps,
                          fresh_compile=fresh,
                          wall_s=lwall)

                which = int(scal[_S_INVMIN])
                if which != _BIG:
                    # invariant violations live in the NEW frontier
                    # (depth+1); the globally LOWEST violated
                    # cfg-invariant index wins, then the first device
                    # holding it
                    aux = np.asarray(aux_d)
                    nm = self.inv_fns[which][0]
                    iv_dev = int(np.argmax(aux[:, _A_INVW] == which))
                    iv_slot = int(aux[iv_dev, _A_INVSLOT])
                    self._ring_levels(tr_rows, tr_src, depth + 1)
                    trace = self._mesh_trace_to(iv_dev, iv_slot,
                                                depth + 1)
                    return self._mk(False, distinct, generated,
                                    depth + 1, t0, warnings,
                                    self._viol("invariant", nm, trace))
                depth += 1
                lvl_frontier = int(scal[_S_FRONT])
                if self._tiers is not None and self._tiers.active and \
                        lvl_frontier > 0:
                    # cold-tier filter (ISSUE 12; supersteps pinned to
                    # 1): drop frontier rows whose keys were spilled —
                    # the rows the uncapped shards would have deduped —
                    # and rewrite the trace-ring slot to match, so the
                    # next level's parent indices keep resolving
                    (frontier, fcount, tr_rows, tr_src, n_dup) = \
                        self._mesh_tier_filter(frontier, fcount,
                                               tr_rows, tr_src,
                                               depth, FC)
                    if n_dup:
                        distinct -= n_dup
                        lvl_frontier -= n_dup
                    self._tiers.publish_gauges(sum_seen)

                if self.max_states and distinct >= self.max_states:
                    # a truncation point IS a level boundary: leave a
                    # checkpoint so the run can be resumed past the
                    # limit
                    if self.checkpoint_path:
                        self._ring_levels(tr_rows, tr_src, depth)
                        self._mesh_ck(seen, np.asarray(seen_count),
                                      frontier, fcount, FC, SC, depth,
                                      generated, distinct)
                    self._save_mesh_profile(SC, FC, TRL, VC)
                    self.log("-- state limit reached, search truncated")
                    return self._mk(
                        True, distinct, generated, depth, t0, warnings,
                        truncated=True,
                        trunc_reason=f"max_states: distinct {distinct} "
                                     f">= limit {self.max_states}")

            now = time.time()
            if now - last_progress >= self.progress_every:
                last_progress = now
                self.log(f"Progress({depth}): {generated} generated, "
                         f"{distinct} distinct, "
                         f"{lvl_frontier} on queue.")
            if self.checkpoint_path and \
                    now - last_ck >= self.checkpoint_every:
                last_ck = now
                self._ring_levels(tr_rows, tr_src, depth)
                self._mesh_ck(seen, np.asarray(seen_count), frontier,
                              fcount, FC, SC, depth, generated,
                              distinct)

        with tel.span("search.finish"):
            if self._ss_fixed is None and not self._ss_shrunk:
                # fast models: remember enough budget to cover the
                # whole search in ONE dispatch on a warm re-run (the
                # early exit stops at the empty frontier, so
                # over-budget is free) — but never after the controller
                # had to shrink: a budget it judged too slow must stay
                # retired
                self._mesh_maxlvl_warm = min(
                    max(depth + 1, self._mesh_maxlvl_warm), _SS_RINGCAP)
            self._save_mesh_profile(SC, FC, TRL, VC)
            if self.checkpoint_path and self.final_checkpoint:
                # COMPLETED-run checkpoint (serve warm resume): an
                # empty frontier over the full seen set
                self._ring_levels(tr_rows, tr_src, depth)
                self._mesh_ck(seen, np.asarray(seen_count),
                              np.zeros((D, FC, PW), np.int32),
                              np.zeros(D, np.int32),
                              FC, SC, depth, generated, distinct)
            self.log("Model checking completed. No error has been "
                     "found.")
            self.log(f"{generated} states generated, {distinct} "
                     f"distinct states found, 0 states left on queue.")
            return self._mk(True, distinct, generated, depth - 1, t0,
                            warnings)

    def _remember_caps(self, SC: int, FC: int, TRL: int,
                       VC: int) -> None:
        """Keep the learned caps on the INSTANCE so warm re-runs (bench
        timed windows) start at them — zero growth redos, zero
        recompiles — exactly like the single-chip resident engine's
        _res_caps."""
        h = self._mesh_caps_hint
        h["SC"] = max(int(h.get("SC", 0)), SC)
        h["FC"] = max(int(h.get("FC", 0)), FC)
        h["TRL"] = max(int(h.get("TRL", 0)), TRL)
        h["GAM16"] = max(int(h.get("GAM16", 0)),
                         int(round(self._a2a_gamma * 16)))
        # MSL is the SETTLED levels-per-dispatch, not a floor: it must
        # follow the controller down when a budget proved too slow
        h["MSL"] = max(1, int(self._mesh_maxlvl_warm))
        h["VC"] = max(int(h.get("VC", 0)), VC)

    def _save_mesh_profile(self, SC: int, FC: int, TRL: int,
                           VC: int) -> None:
        self._remember_caps(SC, FC, TRL, VC)
        caps = {"SC": SC, "FC": FC, "TRL": TRL,
                "GAM16": max(1, int(round(self._a2a_gamma * 16))),
                "MSL": max(1, int(self._mesh_maxlvl_warm))}
        if self._vc_seen_need:
            # persist the OBSERVED need, not the running capacity
            # (which starts at the conservative 4*FC default and only
            # grows): the next process warm-starts its merge at the
            # lean size — the ISSUE 11 merge-wall win — and at worst
            # pays one growth redo if its workload needs more.  The
            # in-process hint (_remember_caps) keeps the capacity so a
            # warm re-run in THIS process never recompiles.  Runs that
            # never observed a need (VC >= R: no padding to compact)
            # save NO VC at all — persisting the 4*FC heuristic would
            # max-merge over a learned lean value and permanently
            # inflate every future rank merge (_MESH_PROFILE_OPT above).
            caps["VC"] = max(FC, _pow2_at_least(
                self._vc_seen_need, lo=256))
        self._save_caps_profile(
            caps, variant=self._profile_variant(),
            keys=_MESH_PROFILE_KEYS, optional=_MESH_PROFILE_OPT)

    # ------------------------------------------------------------------
    # the LEGACY host loop (refinement/temporal PROPERTYs; the
    # JAXMC_MESH_RESIDENT=0 diagnosis escape hatch)
    # ------------------------------------------------------------------

    def _run_hostloop(self, need_edges: bool,
                      need_props: bool) -> CheckResult:
        t0 = time.time()
        tel = obs.current()
        model = self.model
        D, W, K = self.D, self.W, self.K
        warnings = ["mesh backend: dedup on 128-bit fingerprints; "
                    "collision probability < n^2 * 2^-129"]
        warnings.extend(self._temporal_warnings())
        if self.por and self._por_plan() is not None:
            # reachable only via the JAXMC_MESH_RESIDENT=0 escape hatch
            # (refinement/temporal PROPERTYs already refuse in
            # _por_plan): the ample mask lives in the resident
            # superstep's level tail — name the refusal, run unreduced
            self._por_memo = None
            self.por_reason = ("mesh host loop active "
                               "(JAXMC_MESH_RESIDENT=0): the device "
                               "mask lives in the resident superstep")
            obs.current().gauge("por.disabled_reason", self.por_reason)
            obs.current().gauge("por.enabled", False)
            warnings.append(f"--por requested but reduction disabled: "
                            f"{self.por_reason} (running unreduced)")
        # (PROPERTYs without store_trace are refused in __init__; the
        # resume path can also arrive later, through explore()'s override)
        if need_props and self.resume_from:
            raise ModeError(
                "mesh resume with refinement/temporal PROPERTYs is not "
                "supported - use the single-chip device modes")
        warnings.extend(self._symmetry_warnings())

        init_rows, explored_init, n_init, err = \
            self._prepare_init(t0, warnings)
        if err is not None:
            return err
        generated = self._init_generated
        explored_mask = np.zeros(n_init, bool)
        explored_mask[explored_init] = True
        distinct = int(explored_mask.sum())

        self._levels: List[Tuple[np.ndarray, Optional[np.ndarray], int]] \
            = []
        graph = None   # behavior graph (temporal PROPERTYs)
        fsids = None   # flat (d*FC + slot) -> graph state id

        if self.resume_from:
            ck = self._load_ck("mesh")
            if ck["D"] != D:
                raise ValueError(
                    f"cannot resume: checkpoint has {ck['D']} devices, "
                    f"mesh has {D}")
            FC, SC = ck["FC"], ck["SC"]
            depth = ck["depth"]
            generated = ck["generated"]
            distinct = ck["distinct"]
            seen = self._put(ck["seen"])
            seen_counts = ck["seen_counts"].astype(np.int64)
            frontier = self._put(ck["frontier"])
            fcount = self._put(ck["fcount"])
            if ck.get("levels") is not None:
                self._levels = ck["levels"]
            elif self.store_trace:
                # advisor r3: match _restore_ck_state — a user expecting
                # traces must hear it up front, not get an empty-trace
                # violation later
                raise ValueError(
                    "cannot resume with traces: the checkpoint was "
                    "written with --no-trace")
            self.log(f"Resuming mesh run at depth {depth} "
                     f"({distinct} distinct states)")
        else:
            init_keys, init_packed, init_povf = \
                self._host_keys(init_rows)
            if init_povf:
                from ..compile.vspec import CompileError
                raise CompileError(self._pack_ovf_msg())
            owner = self._owner_from_keys(init_keys)
            per_dev = [init_rows[(owner == d) & explored_mask]
                       for d in range(D)]
            FC = _pow2_at_least(
                max(max((len(p) for p in per_dev), default=1), 1), lo=64)
            SC = _pow2_at_least(4 * FC, lo=256)
            explored_idx = np.nonzero(explored_mask)[0]
            seen, frontier, fcount, init_scounts = self._init_shards(
                init_rows, explored_idx, D, SC, FC,
                keys=init_keys, packed=init_packed, owner=owner)
            if self.live_obligations:
                graph = _LiveGraph(self.labels_flat, self.collect_edges)
                graph.add_inits(init_packed, explored_idx)
                # (d, slot) -> behavior-graph state id, flat [D*FC]
                fsids = np.full(D * FC, -1, np.int64)
                for d in range(D):
                    for i in range(int(fcount[d])):
                        fsids[d * FC + i] = graph.sid_by_key[
                            frontier[d, i].tobytes()]
            if self.store_trace:
                self._levels.append((frontier.copy(), None, FC))
            frontier = self._put(frontier)
            seen = self._put(seen)
            fcount = self._put(fcount)
            seen_counts = init_scounts.astype(np.int64)
            depth = 0

        last_progress = last_ck = time.time()
        lvl_frontier = int(np.sum(np.asarray(fcount)))
        while lvl_frontier > 0:
            lvl_t0 = time.time()
            lvl_gen0 = generated
            C = self.A * FC
            need = int(seen_counts.max(initial=0)) + D * C
            if need > SC:
                SC2 = _pow2_at_least(need, SC)
                pad = np.full((D, SC2 - SC, K), SENTINEL, np.int32)
                pad[:, :, 0] = 1
                seen = jnp.concatenate([seen, self._put(pad)], axis=1)
                SC = SC2
            expanding_FC = FC
            while True:
                step = self._get_mesh_step(SC, FC)
                outs = step(seen,
                            self._put(seen_counts.astype(np.int32)),
                            frontier, fcount)
                # count THIS attempt's exchange with the gamma it ran
                # at: gamma-doubling reruns each pay a full exchange
                # (review r8)
                B_att = self._a2a_bucket(C, FC) \
                    if self.exchange == "a2a" else 0
                tel.counter("mesh.exchange_bytes", self._exchange_bytes(
                    C, B_att,
                    self._a2a_spill_bucket(B_att) if B_att else 0))
                (seen2_, seen_cnt, front_rows, front_cnt, front_src,
                 tot_gen, tot_new, dead_local, dead_slot, assert_local,
                 asrt_a, asrt_f, any_ovf, inv_which, inv_slot,
                 tot_front, a2a_ovf, tot_spill) = outs[:18]
                if self.exchange == "a2a" and \
                        bool(np.asarray(a2a_ovf)[0]):
                    # hash skew exceeded the per-peer bucket AND the
                    # spill pass: rerun the level with doubled capacity
                    # factor (inputs are untouched — the step is
                    # functional)
                    self._a2a_gamma *= 2
                    self.log(f"-- mesh: a2a bucket+spill overflow, "
                             f"gamma -> {self._a2a_gamma}")
                    continue
                seen = seen2_
                break
            self._spill_rows += int(np.asarray(tot_spill)[0])

            ovc = int(np.asarray(any_ovf)[0])
            if ovc:
                if ovc == OV_DEMOTED:
                    msg = ("a demoted compile-recovery fired (the "
                           "kernel under-approximates here): run the "
                           "host_seen mode, which demotes the arm to "
                           "the interpreter and restarts — raising "
                           "caps cannot help")
                elif ovc == OV_PACK:
                    msg = self._pack_ovf_msg()
                else:
                    msg = ("a container exceeded its lane capacity "
                           f"({self._caps_note()}); counts would no "
                           "longer be exact")
                return self._mk(False, distinct, generated, depth, t0,
                                warnings, Violation(
                                    "error", "capacity overflow", [],
                                    msg))
            dead_np = np.asarray(dead_local)
            if model.check_deadlock and dead_np.any():
                dv = int(np.argmax(dead_np))
                ds = int(np.asarray(dead_slot)[dv])
                trace = self._mesh_trace_to(dv, ds, depth)
                return self._mk(False, distinct, generated, depth, t0,
                                warnings,
                                self._viol("deadlock", "deadlock", trace))
            assert_np = np.asarray(assert_local)
            if assert_np.any():
                av = int(np.argmax(assert_np))
                aa = int(np.asarray(asrt_a)[av])
                af = int(np.asarray(asrt_f)[av])
                trace = self._mesh_trace_to(av, af, depth)
                return self._mk(
                    False, distinct, generated, depth, t0, warnings,
                    self._viol("assert", "Assert", trace,
                               f"assertion in {self.labels_flat[aa]}"))

            ecand = eexp = esrc = None
            if need_edges:
                # the exchanged candidate stream (revisits included):
                # gather mode replicates it on every device (read device
                # 0); a2a routes disjoint buckets (concatenate all)
                if self.exchange == "a2a":
                    ecand = np.asarray(outs[18]).reshape(-1, self.PW)
                    eexp = np.asarray(outs[19]).reshape(-1)
                    esrc = np.asarray(outs[20]).reshape(-1)
                else:
                    ecand = np.asarray(outs[18][0])
                    eexp = np.asarray(outs[19][0])
                    esrc = np.asarray(outs[20][0])
                if self.refiners:
                    fr_np = np.asarray(frontier)
                    rv = self._mesh_refine_edges(fr_np, ecand, eexp,
                                                 esrc, expanding_FC,
                                                 depth)
                    if rv is not None:
                        return self._mk(False, distinct, generated,
                                        depth, t0, warnings, rv)

            generated += int(np.asarray(tot_gen)[0])
            distinct += int(np.asarray(tot_new)[0])
            seen_counts = np.asarray(seen_cnt).astype(np.int64)
            tel.level(depth, frontier=lvl_frontier,
                      generated=generated - lvl_gen0,
                      new=int(np.asarray(tot_new)[0]), distinct=distinct,
                      seen=int(seen_counts.sum()), devices=D,
                      wall_s=round(time.time() - lvl_t0, 6))
            self._fp_occupancy = int(seen_counts.sum())
            if seen_counts.sum():
                self._shard_balance = float(
                    seen_counts.max() / (seen_counts.sum() / D))
            max_front = int(np.asarray(front_cnt).max(initial=0))
            # device->host frontier copies only when something needs
            # them (tracing, a violation to localize, or FC regrowth):
            # in the perf configuration (store_trace=False, clean level)
            # the frontier never leaves the device
            iw = np.asarray(inv_which)
            which = int(iw.min())
            need_host_rows = (self.store_trace or max_front > FC or
                              which != _BIG or graph is not None)
            front_rows_np = np.asarray(front_rows) if need_host_rows \
                else None
            if self.store_trace:
                # trim to the occupied prefix: keeping full G = D*A*FC
                # capacity per level would hold the padded expansion of
                # the whole search in host RAM
                keep = max(max_front, 1)
                self._levels.append(
                    (front_rows_np[:, :keep],
                     np.asarray(front_src)[:, :keep], expanding_FC))

            sids_per_dev = None
            if graph is not None:
                # behavior-graph bookkeeping: kept new rows register with
                # provenance a*(D*FCprev) + (d_src*FCprev + f) so
                # labels_flat and the flat parent-sid table resolve them;
                # then every explored candidate edge (revisits included)
                front_src_np = np.asarray(front_src)
                fcnt_np = np.asarray(front_cnt)
                Cprev = self.A * expanding_FC
                flat_rows, flat_prov, row_counts = [], [], []
                for d in range(D):
                    n = int(fcnt_np[d])
                    row_counts.append(n)
                    for i in range(n):
                        g = int(front_src_np[d, i])
                        d_src, cc = g // Cprev, g % Cprev
                        a, f = cc // expanding_FC, cc % expanding_FC
                        flat_rows.append(front_rows_np[d, i])
                        flat_prov.append(
                            a * (D * expanding_FC)
                            + d_src * expanding_FC + f)
                new_sids = graph.add_level(
                    np.asarray(flat_rows) if flat_rows
                    else np.zeros((0, self.PW), np.int32),
                    np.asarray(flat_prov, np.int64),
                    D * expanding_FC, fsids)
                if graph.collect_edges and ecand is not None:
                    eidx = np.nonzero(eexp)[0]
                    epar = np.empty(len(eidx), np.int64)
                    for k, c in enumerate(eidx):
                        g = int(esrc[c])
                        d_src, cc = g // Cprev, g % Cprev
                        epar[k] = d_src * expanding_FC + cc % expanding_FC
                    graph.add_edges(ecand[eidx], epar, fsids)
                sids_per_dev = []
                off = 0
                for d in range(D):
                    sids_per_dev.append(new_sids[off:off + row_counts[d]])
                    off += row_counts[d]

            if which != _BIG:
                nm = self.inv_fns[which][0]
                iv_dev = int(np.argmax(iw == which))
                iv_slot = int(np.asarray(inv_slot)[iv_dev])
                trace = self._mesh_trace_to(iv_dev, iv_slot, depth + 1)
                return self._mk(False, distinct, generated, depth + 1, t0,
                                warnings,
                                self._viol("invariant", nm, trace))
            depth += 1

            # next frontier: per-device kept rows; capacity grows to the
            # max shard (hash skew can route up to G rows to one device)
            fcount = front_cnt
            if max_front > FC:
                FC = _pow2_at_least(max_front, FC)
                k = min(front_rows_np.shape[1], FC)
                nf = np.full((D, FC, self.PW), SENTINEL, np.int32)
                nf[:, :k] = front_rows_np[:, :k]
                frontier = self._put(nf)
            else:
                frontier = front_rows[:, :FC]
            if graph is not None:
                # flat sid table for the NEXT level's frontier slots
                # (kept-row order is preserved by the compactions above)
                fsids = np.full(D * FC, -1, np.int64)
                for d in range(D):
                    for i, sid in enumerate(sids_per_dev[d]):
                        fsids[d * FC + i] = sid

            if self.max_states and distinct >= self.max_states:
                # a truncation point IS a level boundary: leave a
                # checkpoint so the run can be resumed past the limit
                if self.checkpoint_path:
                    self._mesh_ck(seen, seen_counts, frontier, fcount,
                                  FC, SC, depth, generated, distinct)
                self.log("-- state limit reached, search truncated")
                return self._mk(
                    True, distinct, generated, depth, t0, warnings,
                    truncated=True,
                    trunc_reason=f"max_states: distinct {distinct} >= "
                                 f"limit {self.max_states}")

            now = time.time()
            if now - last_progress >= self.progress_every:
                last_progress = now
                self.log(f"Progress({depth}): {generated} generated, "
                         f"{distinct} distinct, "
                         f"{int(np.asarray(tot_front)[0])} on queue.")
            if self.checkpoint_path and \
                    now - last_ck >= self.checkpoint_every:
                last_ck = now
                self._mesh_ck(seen, seen_counts, frontier, fcount, FC,
                              SC, depth, generated, distinct)
            lvl_frontier = int(np.sum(np.asarray(fcount)))

        if graph is not None:
            viol = self._check_live(graph, warnings)
            if viol is not None:
                return self._mk(False, distinct, generated, depth - 1,
                                t0, warnings, viol)
        self.log("Model checking completed. No error has been found.")
        self.log(f"{generated} states generated, {distinct} distinct "
                 f"states found, 0 states left on queue.")
        return self._mk(True, distinct, generated, depth - 1, t0, warnings)

    def _mk(self, ok, distinct, generated, diameter, t0, warnings,
            violation=None, truncated=False, drained=False,
            trunc_reason=None):
        tel = obs.current()
        self._por_finish(self._por_stats["ample"],
                         self._por_stats["expanded"],
                         self._por_stats["masked"], distinct)
        tel.high_water("device.mem_high_water_bytes",
                       obs.device_mem_high_water())
        occ = getattr(self, "_fp_occupancy", None)
        if occ is not None:
            tel.gauge("fingerprint.occupancy", occ)
        if self.exchange == "a2a":
            tel.gauge("mesh.a2a_gamma", round(self._a2a_gamma, 4))
            tel.gauge("mesh.a2a_spill", self._spill_rows)
            if self._max_bucket:
                tel.gauge("mesh.a2a_max_bucket", self._max_bucket)
        if self._shard_balance is not None:
            tel.gauge("mesh.shard_balance",
                      round(self._shard_balance, 4))
        # where the tables really live: per-device peak allocation
        # (accelerators; XLA:CPU reports none) — shards placed at
        # creation keep every device near the mean
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.mesh.devices.flat]
        if all(p is not None for p in peaks):
            tel.gauge("mesh.device_peak_bytes", peaks)
        if self._supersteps:
            # host_syncs counts SUPERSTEPS (one scalar-ring read per
            # dispatch); the gauge records the deepest fused dispatch
            tel.gauge("mesh.supersteps", self._supersteps)
            tel.gauge("mesh.superstep_levels",
                      self._superstep_levels_max)
        # ISSUE 12 result surface (mirrors bfs._mk_result): tier
        # summary, fingerprint collision bound, named truncations
        tiers_stats = self._tiers_result(occ)
        n = float((occ or 0) + (len(self._tiers)
                                if self._tiers is not None else 0))
        collision_p = n * n * 2.0 ** -129
        tel.gauge("fingerprint.collision_p", collision_p)
        if truncated and trunc_reason is None:
            trunc_reason = "drain" if drained else "unattributed"
        if trunc_reason:
            tel.gauge("truncation.reason", trunc_reason)
        return CheckResult(ok=ok, distinct=distinct, generated=generated,
                           diameter=max(diameter, 0), violation=violation,
                           wall_s=time.time() - t0, truncated=truncated,
                           warnings=warnings, drained=drained,
                           trunc_reason=trunc_reason,
                           seen_mode="fingerprint",
                           collision_p=collision_p, tiers=tiers_stats)
