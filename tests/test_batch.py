r"""Cross-model vmapped batching (ISSUE 13).

Covers the acceptance surface:
  - parse-time compatibility: liftable-constant analysis + batch_sig
    equality across the batchtoy family (and inequality elsewhere);
  - the vmapped engine: 4 layout-compatible NON-identical jobs through
    ONE compiled program (occupancy 4, one engine build), per-job
    counts/diameters/violations/traces byte-identical to solo runs —
    including the mixed batch where one member violates while the
    others run to exhaustion;
  - serve fleet wiring: cold-spool cohort pops by bsig and runs as one
    vbatch; artifacts carry the batch block + cost estimate; fast-lane
    jobs jump the queue;
  - the claimed-follower race and the warm-registry sig-lock eviction
    race (ISSUE 13 bugfix), pinned with concurrency tests;
  - chaos: mid-batch drain parks members as drained and the next
    daemon life re-answers them with identical counts; device-owner
    death requeues (never loses) the in-flight cohort and respawns.
"""

import os
import threading
import time

import pytest

from jaxmc import drain
from jaxmc.engine.explore import Explorer, format_trace
from jaxmc.serve import JobQueue, ServeDaemon
from jaxmc.serve.protocol import ServeClient, build_config, job_signature
from jaxmc.session import SessionConfig, batch_profile, load_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = os.path.join(REPO, "specs")
BT = os.path.join(SPECS, "batchtoy.tla")


def btcfg(v):
    return os.path.join(SPECS, f"batchtoy_{v}.cfg")


JAX_OPTS = {"backend": "jax", "platform": "cpu", "host_seen": True}


def session_cfg(v, **kw):
    return SessionConfig(spec=BT, cfg=btcfg(v), backend="jax",
                         platform="cpu", host_seen=True, **kw)


@pytest.fixture(autouse=True)
def _clean_drain():
    drain.clear()
    yield
    drain.clear()


_SOLO_CACHE = {}


def _solo(v):
    """Solo host_seen reference run, cached per variant — every parity
    assertion reuses one engine build (builds dominate suite wall)."""
    if v not in _SOLO_CACHE:
        from jaxmc.backend.bfs import TpuExplorer
        m = load_model(BT, btcfg(v), False)
        _SOLO_CACHE[v] = TpuExplorer(m, host_seen=True).run()
    return _SOLO_CACHE[v]


def _result_tuple(r):
    viol = None
    if r.violation is not None:
        viol = (r.violation.kind, r.violation.name,
                format_trace(r.violation))
    return (r.ok, r.distinct, r.generated, r.diameter,
            bool(r.truncated), viol)


class TestCompat:
    def test_batchtoy_constants_all_liftable(self):
        from jaxmc.analyze.bounds import liftable_constants
        for v in ("a", "b", "c", "bad"):
            m = load_model(BT, btcfg(v), False)
            assert liftable_constants(m) == \
                ("Bound", "Limit", "Step", "WrapCap")

    def test_view_constants_pinned(self):
        # constants reachable from a cfg VIEW feed the dedup-key basis
        # outside the const-lane install sites: never liftable
        from jaxmc.analyze.bounds import liftable_constants
        m = load_model(os.path.join(SPECS, "viewtoy.tla"),
                       os.path.join(SPECS, "viewtoy.cfg"), False)
        for n in m.cfg.constants:
            assert n not in liftable_constants(m) or \
                m.view is None

    def test_batch_profile_equality(self):
        profs = [batch_profile(session_cfg(v))
                 for v in ("a", "b", "c", "bad")]
        assert all(p is not None for p in profs)
        assert len({p.bsig for p in profs}) == 1
        assert profs[0].lift == ("Bound", "Limit", "Step", "WrapCap")
        # the analyze cost estimate rides the profile (fast-lane oracle)
        assert all(isinstance(p.cost_estimate, int) for p in profs)

    def test_batch_profile_separates_other_models_and_options(self):
        base = batch_profile(session_cfg("a"))
        other = batch_profile(SessionConfig(
            spec=os.path.join(SPECS, "transfer_scaled.tla"),
            backend="jax", platform="cpu", host_seen=True))
        assert other is None or other.bsig != base.bsig
        opt = batch_profile(session_cfg("a", max_states=7))
        assert opt.bsig != base.bsig
        # non-batchable configurations profile to None, never crash
        assert batch_profile(SessionConfig(spec=BT, cfg=btcfg("a"))) \
            is None  # interp backend
        assert batch_profile(session_cfg("a")) is not None


class TestVmappedEngine:
    @pytest.fixture(scope="class")
    def batch_run(self):
        from jaxmc.backend.batch import BatchCheckEngine
        cfgs = [session_cfg(v) for v in ("a", "b", "c", "bad")]
        be = BatchCheckEngine(cfgs).build()
        members = be.run()
        return be, members

    def test_one_engine_serves_all(self, batch_run):
        be, members = batch_run
        donor = members[0].engine
        # followers share the donor's compiled kernels + caches — zero
        # extra engine builds (the "one compile" criterion)
        for mem in members[1:]:
            assert mem.engine.compiled is donor.compiled
            assert mem.engine.layout is donor.layout
            assert mem.engine._hstep_cache is donor._hstep_cache
        assert be.dispatcher.max_width == 4
        assert be.dispatcher.dispatches > 0
        assert be.lift_names == ("Bound", "Limit", "Step", "WrapCap")

    def test_per_member_solo_parity(self, batch_run):
        _be, members = batch_run
        for v, mem in zip(("a", "b", "c", "bad"), members):
            assert mem.error is None, f"{v}: {mem.error}"
            assert _result_tuple(mem.result) == \
                _result_tuple(_solo(v)), v

    def test_mixed_batch_verdicts(self, batch_run):
        # one member violates; the others run to exhaustion — the
        # continuous-batching membership change between supersteps
        _be, members = batch_run
        ok = {v: m.result for v, m in
              zip(("a", "b", "c", "bad"), members)}
        assert ok["bad"].violation is not None
        assert ok["bad"].violation.kind == "invariant"
        assert ok["bad"].violation.name == "InBound"
        for v in ("a", "b", "c"):
            assert ok[v].ok and ok[v].violation is None
            assert not ok[v].truncated

    def test_which_supersteps_have_which_width(self, batch_run):
        # the cohort the parity tests above run is ragged: a level is
        # one chunk here, so a member rides one lane a level until it
        # is done.  `bad` violates at level 5 and leaves after five
        # supersteps of width 4; `c` (diameter 19) exhausts after 20,
        # `a` (27) after 28, and `b` (39) runs the last twelve ALONE:
        # one live lane beside three idle ones, whose block is the only
        # one fetched (ISSUE 40)
        be, members = batch_run
        assert [m.result.diameter for m in members] == [27, 39, 19, 5]
        assert be.dispatcher.widths == \
            [4] * 5 + [3] * 15 + [2] * 8 + [1] * 12
        assert be.dispatcher.dispatches == 40

    @pytest.fixture(scope="class")
    def superstep(self, batch_run):
        """A dispatcher of the cohort's own (fresh barrier state, its
        own recorder), one real stacked input — the widest superstep's
        of a re-run, all four lanes live — and what `vmap(hstep_core)`
        itself answers for it."""
        import jax
        import numpy as np
        from jaxmc import obs
        from jaxmc.backend.batch import BatchDispatcher
        be, members = batch_run
        seen = []
        real = be.dispatcher._vstep

        def spy(fr, fc, cv):
            seen.append((np.asarray(fr), np.asarray(fc)))
            return real(fr, fc, cv)

        be.dispatcher._vstep = spy
        try:
            be.run()
        finally:
            be.dispatcher._vstep = real
        fr, fc = max((x for x in seen if (x[1] > 0).all()),
                     key=lambda x: int(x[1].sum()))
        cvecs = np.stack([m.engine._cvec for m in members])
        disp = BatchDispatcher(members[0].engine, cvecs,
                               tel=obs.Telemetry())
        ref = jax.jit(jax.vmap(disp._core))(fr, fc, disp._cvecs)
        return disp, fr, fc, {k: np.asarray(v) for k, v in ref.items()}

    @pytest.mark.parametrize("slots", [(2,), (1, 3), (0, 1, 3),
                                       (0, 1, 2, 3)],
                             ids=lambda s: f"width{len(s)}")
    def test_pack_fetch_unpack_is_the_identity(self, superstep, slots):
        # what a member is handed answers all nine names with the
        # dtypes, shapes and values of vmap(hstep_core)'s own outputs:
        # the full block (width 4) and the live lanes alone (1-3),
        # through the members' own surface, one thread a live member
        import numpy as np
        disp, fr, fc, ref = superstep
        disp.reset()
        for s in set(range(disp.B)) - set(slots):
            disp.deregister(s)
        c0 = dict(disp.tel.counters)
        got = {}

        def member(s):
            got[s] = disp.hstep_factory(s)(disp.CH)(fr[s], fc[s])

        ts = [threading.Thread(target=member, args=(s,)) for s in slots]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert sorted(got) == list(slots)
        assert disp.widths == [len(slots)]
        for s in slots:
            assert sorted(got[s]) == sorted(ref)
            for k, want in ref.items():
                have = np.asarray(got[s][k])
                assert have.dtype == want.dtype, (k, have.dtype)
                assert have.shape == want[s].shape, (k, have.shape)
                assert np.array_equal(have, want[s]), (s, k)
        assert int(ref["gen"].sum()) > 0 and ref["cvalid"].any()
        # ONE transfer, of the live lanes' blocks and nothing else
        c = disp.tel.counters
        assert c["batch.fetch_transfers"] - \
            c0.get("batch.fetch_transfers", 0) == 1
        rows = disp.PW + ref["keys"].shape[-1] + 1
        lane_mb = rows * (ref["cvalid"].shape[-1] + 128) * 4 / 1e6
        assert c["batch.fetch_mb"] - c0.get("batch.fetch_mb", 0.0) == \
            pytest.approx(len(slots) * lane_mb)

    def test_a_failed_dispatch_reaches_every_waiting_member(
            self, superstep):
        # `_fire_locked`'s except branch: the thread that completes the
        # barrier runs the dispatch, and its failure is handed to EVERY
        # member as that member's own error — nobody is left waiting
        # on a lane that cannot fire again
        disp, fr, fc, _ref = superstep
        disp.reset()
        real = disp._vstep

        def boom(*_a):
            raise FloatingPointError("injected: the device fell over")

        disp._vstep = boom
        n0 = disp.tel.counters.get("batch.dispatches", 0)
        errs = {}

        def member(s):
            try:
                disp.hstep_factory(s)(disp.CH)(fr[s], fc[s])
            except BaseException as ex:  # noqa: BLE001
                errs[s] = ex
            finally:
                disp.deregister(s)

        ts = [threading.Thread(target=member, args=(s,), daemon=True)
              for s in range(disp.B)]
        try:
            for t in ts:
                t.start()
            for t in ts:
                t.join(30)
            assert not [t for t in ts if t.is_alive()], "deadlock"
        finally:
            disp._vstep = real
        assert sorted(errs) == list(range(disp.B))
        for ex in errs.values():
            assert isinstance(ex, RuntimeError)
            assert "vmapped batch dispatch failed" in str(ex)
            assert isinstance(ex.__cause__, FloatingPointError)
        assert disp.dispatches == 0 and not disp._results
        # nothing was fetched, and nothing says it was
        assert disp.tel.counters.get("batch.fetch_transfers", 0) == \
            disp.tel.counters.get("batch.dispatches", 0) == n0

    def test_member_counts_differ(self, batch_run):
        # NON-identical jobs: the whole point vs PR 7's coalescing
        _be, members = batch_run
        assert len({m.result.distinct for m in members}) == 4

    def test_interp_parity(self, batch_run):
        _be, members = batch_run
        for v, mem in zip(("a", "b", "c"), members):
            exp = Explorer(load_model(BT, btcfg(v), False)).run()
            assert (mem.result.distinct, mem.result.generated) == \
                (exp.distinct, exp.generated)

    def test_deep_rung_cohort_parity_cold_and_warm(self):
        # the deep-narrow batchtoy_bench1-4 rungs (hundreds of levels, a
        # handful of states each: the dispatch-bound shape a cohort is
        # for; the warm leg of the deleted `make batch-check`, ISSUE 43):
        # every member's answer is its solo run's, the cohort rides one
        # program at full width, and a second run on the WARM engine
        # repeats the answer
        from jaxmc.backend.batch import BatchCheckEngine
        from jaxmc.backend.bfs import TpuExplorer
        names = [f"bench{i}" for i in (1, 2, 3, 4)]
        solos = [TpuExplorer(load_model(BT, btcfg(v), False),
                             host_seen=True, store_trace=False).run()
                 for v in names]
        assert len({r.distinct for r in solos}) == 4
        be = BatchCheckEngine([session_cfg(v, no_trace=True)
                               for v in names]).build()
        for _ in range(2):
            members = be.run()
            for v, solo, mem in zip(names, solos, members):
                assert mem.error is None, f"{v}: {mem.error}"
                assert _result_tuple(mem.result) == _result_tuple(solo), v
            assert be.dispatcher.max_width == 4

    def test_incompatible_cohort_refused(self):
        from jaxmc.backend.batch import (BatchCheckEngine,
                                         BatchIncompatible)
        cfgs = [session_cfg("a"),
                SessionConfig(spec=os.path.join(SPECS,
                                                "transfer_scaled.tla"),
                              backend="jax", platform="cpu",
                              host_seen=True)]
        with pytest.raises(BatchIncompatible):
            BatchCheckEngine(cfgs).build()


#: ISSUE 39: what `liftable_constants` walks.  One module, four ways a
#: constant can reach a quantifier's domain (a static-only position: the
#: kernel enumerates it at trace time, with the DONOR's concrete value)
#: or stay out of one; (Next's body, the constants that lift)
_LIFT_HEAD = ("---- MODULE lw ----\nEXTENDS Naturals\n"
              "CONSTANTS K, M\nVARIABLES x\n"
              "Init == x \\in 1..M\n")
_LIFT_TAIL = ("Spec == Init /\\ [][Next]_x\n"
              "Inv == x <= 10 + M\n====\n")
_LIFT_CASES = {
    # a bare reference to the operator that holds the domain
    "bare-reference": (
        "Step == \\E d \\in 1..K : x + d <= 10 /\\ x' = x + d\n"
        "Next == Step \\/ (x' = x)\n", ("M",)),
    # the domain itself behind a bare reference
    "domain-by-name": (
        "Dom == 1..K\n"
        "Next == (\\E d \\in Dom : x + d <= 10 /\\ x' = x + d)"
        " \\/ (x' = x)\n", ("M",)),
    # an applied operator whose RESULT is the domain's end
    "applied-in-domain": (
        "Lim(y) == K + y\n"
        "Next == (\\E d \\in 1..Lim(0) : x + d <= 10 /\\ x' = x + d)"
        " \\/ (x' = x)\n", ("M",)),
    # value positions only, behind the same bare reference: K lifts
    "value-only": (
        "Step == x + K <= 10 /\\ x' = x + K\n"
        "Next == Step \\/ (x' = x)\n", ("K", "M")),
}


def _lift_model(tmp_path, case, k=2, m=3, form="SPECIFICATION Spec"):
    spec = str(tmp_path / "lw.tla")
    with open(spec, "w") as f:
        f.write(_LIFT_HEAD + _LIFT_CASES[case][0] + _LIFT_TAIL)
    cfg = str(tmp_path / f"k{k}m{m}{form[:4]}.cfg")
    with open(cfg, "w") as f:
        f.write(f"{form}\nINVARIANT Inv\nCONSTANTS\n  K = {k}\n"
                f"  M = {m}\n")
    return spec, cfg


class TestLiftWalk:
    """What a cohort rests on (ISSUE 39): a constant lifts only where
    no position that reaches it is static.  Up to PR 38 a bare
    reference to a parameterless operator was not followed (so `K`
    under `Step` lifted) and the build raised nothing: the static path
    reads the donor's concrete value, and the other members were
    checked against the donor's domain."""

    @pytest.mark.parametrize("form", ["SPECIFICATION Spec",
                                      "INIT Init\nNEXT Next"],
                             ids=["specification", "init-next"])
    @pytest.mark.parametrize("case", sorted(_LIFT_CASES))
    def test_what_lifts(self, tmp_path, case, form):
        from jaxmc.analyze.bounds import liftable_constants
        spec, cfg = _lift_model(tmp_path, case, form=form)
        # M shapes Init alone (`1..M`) and is a value in Inv: it lifts
        # under both forms of cfg — no device program is traced from
        # Init, every member enumerates its own init states on the host
        assert liftable_constants(load_model(spec, cfg, True)) == \
            _LIFT_CASES[case][1]

    @pytest.mark.parametrize("case", ["bare-reference", "domain-by-name",
                                      "applied-in-domain"])
    def test_a_constant_in_a_domain_never_rides_a_lane(self, tmp_path,
                                                       case):
        from jaxmc.backend.batch import BatchCheckEngine, \
            BatchIncompatible
        pairs = [_lift_model(tmp_path, case, k=k) for k in (2, 3)]
        cfgs = [SessionConfig(spec=s, cfg=c, backend="jax",
                              platform="cpu", host_seen=True,
                              no_deadlock=True) for s, c in pairs]
        # two classes: the daemon never claims them together ...
        assert len({batch_profile(c).bsig for c in cfgs}) == 2
        # ... and the engine refuses them where it is handed both (the
        # parent built them and answered K = 3 with K = 2's counts)
        with pytest.raises(BatchIncompatible, match="constant K"):
            BatchCheckEngine(cfgs).build()
        exact = [Explorer(load_model(s, c, True)).run()
                 for s, c in pairs]
        assert exact[0].generated != exact[1].generated

    @pytest.mark.parametrize("form", ["SPECIFICATION Spec",
                                      "INIT Init\nNEXT Next"],
                             ids=["specification", "init-next"])
    def test_a_constant_of_init_alone_rides_a_lane_exactly(self, tmp_path,
                                                           form):
        from jaxmc.backend.batch import BatchCheckEngine
        pairs = [_lift_model(tmp_path, "bare-reference", m=m, form=form)
                 for m in (2, 3, 5)]
        cfgs = [SessionConfig(spec=s, cfg=c, backend="jax",
                              platform="cpu", host_seen=True,
                              no_deadlock=True) for s, c in pairs]
        assert len({batch_profile(c).bsig for c in cfgs}) == 1
        be = BatchCheckEngine(cfgs).build()
        assert be.lift_names == ("M",)
        for mem, (s, c) in zip(be.run(), pairs):
            exact = Explorer(load_model(s, c, True)).run()
            assert mem.error is None
            assert (mem.result.generated, mem.result.distinct,
                    mem.result.diameter, mem.result.ok) == \
                (exact.generated, exact.distinct, exact.diameter,
                 exact.ok)


class TestStructuralMerge:
    """Structural batch-bound merge (ISSUE 18): the donor keeps
    per-element EB trees — the interval-union over members — instead of
    collapsing every container to a whole-variable summary, so the
    shared plan never packs wider than the worst solo member."""

    def _eb(self, **kw):
        from jaxmc.analyze.bounds import EB
        return EB(**kw)

    def test_merge_eb_interval_union(self):
        from jaxmc.analyze.bounds import merge_eb
        a = self._eb(all=(0, 2), rng=self._eb(all=(0, 2)))
        b = self._eb(all=(1, 5), rng=self._eb(all=(1, 5)))
        m = merge_eb(a, b)
        assert m.all == (0, 5)
        assert m.rng.all == (0, 5)

    def test_merge_eb_none_child_drops(self):
        # a child proven on only one side is NOT kept: the consumer
        # falls back to the merged covering interval, a superset for
        # both members — never a narrower guess
        from jaxmc.analyze.bounds import merge_eb
        a = self._eb(all=(0, 2), rng=self._eb(all=(0, 2)))
        b = self._eb(all=(0, 9))
        m = merge_eb(a, b)
        assert m.all == (0, 9) and m.rng is None
        assert merge_eb(a, None) is None

    def test_merge_eb_keys_intersect(self):
        from jaxmc.analyze.bounds import merge_eb
        a = self._eb(all=(0, 3), keys={"x": self._eb(all=(0, 1)),
                                       "y": self._eb(all=(0, 3))})
        b = self._eb(all=(0, 4), keys={"x": self._eb(all=(2, 4))})
        m = merge_eb(a, b)
        assert set(m.keys) == {"x"}
        assert m.keys["x"].all == (0, 4)

    def test_merge_element_bounds_any_none_member(self):
        from jaxmc.analyze.bounds import merge_element_bounds
        d = {"v": self._eb(all=(0, 1))}
        assert merge_element_bounds([d, None]) == {}
        assert merge_element_bounds([]) == {}
        m = merge_element_bounds([d, {"v": self._eb(all=(3, 4)),
                                      "w": self._eb(all=(0, 1))}])
        assert set(m) == {"v"} and m["v"].all == (0, 4)

    def test_merged_bounds_backfills_lane_proofs(self):
        # lane-proven vars without a structured tree still reach pack
        # as a covering EB — the lane precision never regresses
        from jaxmc.backend.batch import _MergedBounds
        mb = _MergedBounds(merged={"v": (0, 5)},
                           merged_eb={"w": self._eb(all=(1, 2))})
        eb = mb.element_bounds()
        assert eb["v"].all == (0, 5) and eb["w"].all == (1, 2)

    @pytest.fixture(scope="class")
    def msgstoy_cohort(self, tmp_path_factory):
        # same module, Cap=2 vs Cap=3: `msgs` is a per-process table,
        # so the donor layout depends on MERGED per-element bounds
        # msgstoy with Tick's filter off Cap: there `Cap` stands in the
        # predicate of a set that is a quantifier's DOMAIN, a pinned
        # position since ISSUE 39 (`liftable_constants` follows the bare
        # reference `Tick` now), and Cap would not lift
        from jaxmc.analyze.bounds import liftable_constants
        from jaxmc.backend.batch import BatchCheckEngine
        tmp = tmp_path_factory.mktemp("msgstoy")
        text = open(os.path.join(SPECS, "msgstoy.tla")).read()
        assert "clock[m] < Cap" in text
        spec = str(tmp / "msgstoy.tla")
        with open(spec, "w") as f:
            f.write(text.replace("clock[m] < Cap", "clock[m] < 2"))
        cfg2, cfg3 = str(tmp / "cap2.cfg"), str(tmp / "cap3.cfg")
        for path, cap in ((cfg2, 2), (cfg3, 3)):
            with open(path, "w") as f:
                f.write("INIT Init\nNEXT Next\nINVARIANT DoneOK\n"
                        "CONSTANTS\n  Procs = {p1, p2, p3}\n"
                        f"  Cap = {cap}\n  T = 2\n  P1 = p1\n")
        assert liftable_constants(load_model(spec, cfg2, False)) == \
            ("Cap",)
        assert liftable_constants(load_model(
            os.path.join(SPECS, "msgstoy.tla"),
            os.path.join(SPECS, "msgstoy.cfg"), False)) == ()
        cfgs = [SessionConfig(spec=spec, cfg=c, backend="jax",
                              platform="cpu", host_seen=True)
                for c in (cfg2, cfg3)]
        be = BatchCheckEngine(cfgs).build()
        members = be.run()
        solos = []
        for c in (cfg2, cfg3):
            from jaxmc.backend.bfs import TpuExplorer
            eng = TpuExplorer(load_model(spec, c, False), host_seen=True)
            solos.append((eng.run(), eng.plan.batch_descriptor()))
        return be, members, solos

    def test_donor_plan_no_wider_than_worst_solo(self, msgstoy_cohort):
        be, members, solos = msgstoy_cohort
        donor = members[0].engine.plan.batch_descriptor()
        worst = max(d["bits_per_state"] for _, d in solos)
        assert donor["bits_per_state"] <= worst
        assert donor["proven_lanes"] >= \
            min(d["proven_lanes"] for _, d in solos)

    def test_donor_keeps_structured_proofs(self, msgstoy_cohort):
        be, members, _solos = msgstoy_cohort
        m0 = members[0].engine.model
        rep = m0._bounds_report
        eb = rep.element_bounds()
        # the union tree: msgs rng covers BOTH members' Cap
        assert eb["msgs"].rng.all == (0, 3)
        # clock never makes lane_bounds (no whole-variable summary)
        # but its structured dom proof survives the merge
        assert "clock" not in rep.lane_bounds()
        assert eb["clock"].dom is not None

    def test_members_match_solo(self, msgstoy_cohort):
        _be, members, solos = msgstoy_cohort
        for mem, (sr, _d) in zip(members, solos):
            assert mem.error is None
            assert _result_tuple(mem.result) == _result_tuple(sr)

    def test_record_cohort_element_merge_beats_lane_union(
            self, tmp_path):
        # the satellite fixture: a record whose fields have wildly
        # different ranges.  The whole-variable union (0,103) widens
        # BOTH fields to 7 bits (14 bits/state); the structural merge
        # keeps small at (0,3) and big at (100,103) — 4 bits/state,
        # exactly the worst solo member's plan
        from jaxmc.analyze.bounds import (infer_state_bounds,
                                          merge_element_bounds,
                                          merge_lane_bounds)
        from jaxmc.backend.batch import BatchCheckEngine
        from jaxmc.backend.bfs import TpuExplorer
        spec = str(tmp_path / "recbatch.tla")
        with open(spec, "w") as f:
            f.write(
                "---------------- MODULE recbatch ----------------\n"
                "EXTENDS Naturals\nCONSTANTS Lim\nVARIABLES r\n"
                "Init == r = [small |-> 0, big |-> 100]\n"
                "BumpS == /\\ r.small < 3\n"
                "         /\\ r' = [r EXCEPT !.small = @ + 1]\n"
                "BumpB == /\\ r.big < Lim\n"
                "         /\\ r' = [r EXCEPT !.big = @ + 1]\n"
                "Next == BumpS \\/ BumpB\n"
                "Spec == Init /\\ [][Next]_<<r>>\n"
                "=================================================\n")
        paths = []
        for tag, lim in (("a", 101), ("b", 103)):
            p = str(tmp_path / f"{tag}.cfg")
            with open(p, "w") as f:
                f.write(f"SPECIFICATION Spec\nCONSTANTS\n"
                        f"  Lim = {lim}\n")
            paths.append(p)
        reports = [infer_state_bounds(load_model(spec, p, True))
                   for p in paths]
        # the lane union widens member a's (0,101) proof AND swallows
        # small's (0,3) into one 7-bit interval...
        assert merge_lane_bounds(
            [r.lane_bounds() for r in reports]) == {"r": (0, 103)}
        # ...while the structural merge keeps each field's own width
        meb = merge_element_bounds(
            [r.element_bounds() for r in reports])
        assert meb["r"].keys["small"].all == (0, 3)
        assert meb["r"].keys["big"].all == (100, 103)

        solos = []
        for p in paths:
            eng = TpuExplorer(load_model(spec, p, True),
                              host_seen=True)
            solos.append((eng.run(), eng.plan.batch_descriptor()))
        cfgs = [SessionConfig(spec=spec, cfg=p, backend="jax",
                              platform="cpu", host_seen=True,
                              no_deadlock=True) for p in paths]
        members = BatchCheckEngine(cfgs).build().run()
        donor = members[0].engine.plan.batch_descriptor()
        worst = max(d["bits_per_state"] for _, d in solos)
        assert donor["bits_per_state"] <= worst
        assert donor["bits_per_state"] < 14  # the lane-union width
        for mem, (sr, _d) in zip(members, solos):
            assert mem.error is None
            assert _result_tuple(mem.result) == _result_tuple(sr)


def prime_spool(spool, variants, opts=JAX_OPTS):
    """Queue one job per variant in a COLD spool (before any daemon
    life), so the first pop claims the whole cohort."""
    q = JobQueue(spool)
    jids = []
    for v in variants:
        cfg = build_config(BT, btcfg(v), opts)
        prof = batch_profile(cfg)
        job = q.new_job(cfg.spec, cfg.cfg, opts, job_signature(cfg),
                        bsig=prof.bsig if prof else None,
                        cost_estimate=prof.cost_estimate
                        if prof else None)
        jids.append(job["id"])
    return jids


class TestServeFleet:
    def test_cold_cohort_one_vbatch(self, tmp_path):
        spool = str(tmp_path / "spool")
        jids = prime_spool(spool, ("a", "b", "c", "bad"))
        d = ServeDaemon(spool, workers=2, quiet=True).start()
        try:
            c = ServeClient("127.0.0.1", d.port)
            recs = {j: c.wait(j, timeout=240) for j in jids}
            for v, j in zip(("a", "b", "c", "bad"), jids):
                solo = _solo(v)
                assert recs[j]["status"] == "done"
                assert recs[j]["ok"] == solo.ok
                assert recs[j]["distinct"] == solo.distinct
                assert recs[j]["generated"] == solo.generated
                assert recs[j]["batch_occupancy"] == 4
            st = d.status()
            assert st["gauges"]["serve.batch_occupancy"] == 4
            assert st["gauges"]["serve.batch_compiles"] == 1
            assert st["counters"]["serve.vbatch_jobs"] == 4
            # artifacts: batch block + cost estimate + trace for the
            # violating member
            code, res = c.result(jids[3])
            assert code == 200
            sv = res["serve"]
            assert sv["batch_occupancy"] == 4
            assert sv["lifted_consts"] == ["Bound", "Limit", "Step",
                                           "WrapCap"]
            assert isinstance(sv["cost_estimate"], int)
            assert res["result"]["violation"]["name"] == "InBound"
            solo_bad = _solo("bad")
            assert res["result"]["trace"] == \
                format_trace(solo_bad.violation)
        finally:
            d.shutdown()

    def test_fast_lane_jumps_queue(self, tmp_path, monkeypatch):
        # batchtoy's proven estimate (~65-95 states) sits under the
        # bound; transfer_scaled's (~768) sits over it
        monkeypatch.setenv("JAXMC_SERVE_FASTLANE_BOUND", "100")
        spool = str(tmp_path / "spool")
        d = ServeDaemon(spool, workers=1, quiet=True).start()
        try:
            c = ServeClient("127.0.0.1", d.port)
            # occupy the single worker so queue order is observable
            # (bench1 compiles + runs for a few seconds)
            code, blocker = c.submit(BT, btcfg("bench1"), JAX_OPTS)
            deadline = time.time() + 60
            while time.time() < deadline and \
                    (d.q.load(blocker["id"]) or {}).get("status") \
                    != "running":
                time.sleep(0.01)
            code, slow = c.submit(
                os.path.join(SPECS, "transfer_scaled.tla"),
                options={"backend": "jax", "platform": "cpu",
                         "host_seen": True, "max_states": 50})
            code, fast = c.submit(BT, btcfg("a"), JAX_OPTS)
            assert fast.get("fast_lane") is True
            with d._cv:
                pending = list(d._pending)
            # the proven-small job queued FIRST despite arriving last
            assert pending.index(fast["id"]) < \
                pending.index(slow["id"])
            assert d.tel.counters.get("serve.fastlane_jobs", 0) >= 1
        finally:
            d.shutdown()

    def test_owner_solo_device_job(self, tmp_path, monkeypatch):
        # owner mode routes SOLO device jobs out of the daemon process
        # too; the result is solo-identical and the record says so
        monkeypatch.setenv("JAXMC_SERVE_DEVICE_OWNER", "1")
        spool = str(tmp_path / "spool")
        d = ServeDaemon(spool, workers=1, quiet=True).start()
        try:
            c = ServeClient("127.0.0.1", d.port)
            code, job = c.submit(BT, btcfg("a"), JAX_OPTS)
            assert code == 200
            rec = c.wait(job["id"], timeout=240)
            assert rec["status"] == "done"
            assert rec["device_owner"] is True
            solo = _solo("a")
            assert rec["distinct"] == solo.distinct
            code, res = c.result(job["id"])
            assert res["serve"]["device_owner"] is True
            assert d.status()["device_owner_pid"] is not None
        finally:
            d.shutdown()

    def test_batch_disabled_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JAXMC_SERVE_BATCH", "0")
        spool = str(tmp_path / "spool")
        jids = prime_spool(spool, ("a", "b"))
        d = ServeDaemon(spool, workers=1, quiet=True).start()
        try:
            c = ServeClient("127.0.0.1", d.port)
            for j in jids:
                assert c.wait(j, timeout=240)["status"] == "done"
            assert d.tel.counters.get("serve.vbatch_jobs", 0) == 0
        finally:
            d.shutdown()


class TestRaces:
    def test_claimed_followers_never_double_run(self, tmp_path):
        # 6 jobs in one compat class, 3 workers racing to pop: every
        # job must land exactly one terminal result, each claimed
        # member registered in _running while in flight
        spool = str(tmp_path / "spool")
        jids = prime_spool(spool, ("a", "b", "c", "a", "b", "c"))
        d = ServeDaemon(spool, workers=3, quiet=True).start()
        try:
            c = ServeClient("127.0.0.1", d.port)
            for j in jids:
                rec = c.wait(j, timeout=240)
                assert rec["status"] == "done", rec
            done = d.tel.counters.get("serve.jobs_done", 0)
            vb = d.tel.counters.get("serve.vbatch_jobs", 0)
            assert done == 6
            assert vb >= 4  # at least one cross-model cohort formed
            # exactly one result artifact per job, written once
            for j in jids:
                assert d.q.load_result(j) is not None
        finally:
            d.shutdown()

    def test_sig_lock_eviction_race_fixed(self, tmp_path):
        # ISSUE 13 bugfix: _locked_sig must hold the REGISTERED lock
        # even when eviction popped + a fresh lock was registered
        # between the fetch and the acquire
        d = ServeDaemon(str(tmp_path / "spool"), workers=1, quiet=True)
        stale = threading.Lock()
        real = d._sig_lock
        first = []

        def fetch(sig):
            if not first:
                first.append(1)
                with d._cv:
                    # simulate: eviction dropped the entry and another
                    # submission re-registered a fresh lock after this
                    # worker fetched `stale`
                    d._sig_locks[sig] = threading.Lock()
                return stale
            return real(sig)

        d._sig_lock = fetch
        with d._locked_sig("s1"):
            with d._cv:
                held = d._sig_locks["s1"]
            assert held.locked(), \
                "worker must end up holding the registered lock"
            assert not stale.locked(), \
                "the stale pre-fetched lock must have been released"
        assert not d._sig_locks["s1"].locked()

    def test_eviction_never_pops_held_sig_lock(self, tmp_path):
        d = ServeDaemon(str(tmp_path / "spool"), workers=1, quiet=True)
        d.warm_max = 0
        lk = d._sig_lock("busy")
        lk.acquire()
        try:
            with d._cv:
                d.warm["busy"] = {"session": None, "completed": True}
                d._evict_warm_locked()
                # held lock -> the sig survives eviction untouched
                assert d._sig_locks.get("busy") is lk
        finally:
            lk.release()


@pytest.mark.chaos
@pytest.mark.slow
class TestChaos:
    # chaos+slow (the pytest.ini pattern): `make chaos` runs these;
    # tier-1 timing stays inside its budget
    def test_drain_mid_batch_then_resume_parity(self, tmp_path):
        # deep cohort, drain mid-flight: members park as drained (no
        # result yet), requeue next life, and the re-run answers with
        # solo-identical counts — a batch can be delayed, never lost
        spool = str(tmp_path / "spool")
        jids = prime_spool(spool, ("bench1", "bench2", "bench3",
                                   "bench4"))
        d = ServeDaemon(spool, workers=2, quiet=True).start()
        deadline = time.time() + 120
        while time.time() < deadline:
            if any(d.q.load(j).get("status") == "running"
                   for j in jids):
                break
            time.sleep(0.02)
        d.initiate_drain("test drain mid-batch")
        d.shutdown()
        statuses = {d.q.load(j).get("status") for j in jids}
        assert statuses <= {"queued", "drained", "done"}, statuses
        # next life: recover() requeues drained members, all complete
        d2 = ServeDaemon(spool, workers=2, quiet=True).start()
        try:
            c = ServeClient("127.0.0.1", d2.port)
            for v, j in zip(("bench1", "bench2", "bench3", "bench4"),
                            jids):
                rec = c.wait(j, timeout=300)
                assert rec["status"] == "done", rec
                solo = _solo(v)
                assert rec["distinct"] == solo.distinct
                assert rec["generated"] == solo.generated
        finally:
            d2.shutdown()

    def test_device_owner_death_requeues_and_respawns(self, tmp_path,
                                                      monkeypatch):
        monkeypatch.setenv("JAXMC_SERVE_DEVICE_OWNER", "1")
        spool = str(tmp_path / "spool")
        jids = prime_spool(spool, ("bench1", "bench2", "bench3",
                                   "bench4"))
        d = ServeDaemon(spool, workers=2, quiet=True).start()
        try:
            import signal as _sig
            # kill the owner while the cohort is in flight
            deadline = time.time() + 180
            killed = False
            while time.time() < deadline and not killed:
                pid = d.owner.pid
                if pid is not None and any(
                        d.q.load(j).get("status") == "running"
                        for j in jids):
                    try:
                        os.kill(pid, _sig.SIGKILL)
                        killed = True
                    except ProcessLookupError:
                        pass
                time.sleep(0.05)
            c = ServeClient("127.0.0.1", d.port)
            for v, j in zip(("bench1", "bench2", "bench3", "bench4"),
                            jids):
                rec = c.wait(j, timeout=300)
                assert rec["status"] == "done", rec
                solo = _solo(v)
                assert rec["distinct"] == solo.distinct
            if killed:
                assert d.tel.counters.get("serve.owner_respawns",
                                          0) >= 1
                assert d.owner.spawns >= 2
        finally:
            d.shutdown()


class TestObs:
    def test_fleet_artifact_highlight_row(self, tmp_path, capsys):
        spool = str(tmp_path / "spool")
        jids = prime_spool(spool, ("a", "b", "c"))
        out = str(tmp_path / "fleet.json")
        d = ServeDaemon(spool, workers=1, quiet=True,
                        metrics_out=out).start()
        c = ServeClient("127.0.0.1", d.port)
        for j in jids:
            c.wait(j, timeout=240)
        d.shutdown()
        import argparse
        import io
        from jaxmc.obs.report import cmd_report
        buf = io.StringIO()
        rc = cmd_report(argparse.Namespace(file=out), out=buf)
        assert rc == 0
        assert "batch[occupancy=3" in buf.getvalue()
