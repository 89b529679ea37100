r"""JAX backend tests: kernel compilation, device BFS, mesh sharding.

Equivalence contract (BASELINE.json): identical reachable-state counts
between BACKEND=interp and BACKEND=jax on full (non-violating) runs; same
verdicts on violating ones. Runs on CPU; conftest provides an 8-device
virtual mesh.
"""

import os

import numpy as np
import pytest

from jaxmc.front.cfg import ModelConfig, parse_cfg
from jaxmc.sem.modules import Loader, bind_model

from conftest import REFERENCE, needs_reference

SPECS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "specs")


def load(path, cfg=None):
    m = Loader([os.path.dirname(os.path.abspath(path))]).load_path(path)
    return bind_model(m, cfg or ModelConfig(specification="Spec"))


@pytest.fixture(scope="module")
def pcal_model():
    cfg = parse_cfg(open(os.path.join(REFERENCE, "pcal_intro.cfg")).read())
    return load(os.path.join(REFERENCE, "pcal_intro.tla"), cfg)


class TestLayout:
    @needs_reference
    def test_roundtrip(self, pcal_model):
        from jaxmc.compile.vspec import Bounds
        from jaxmc.compile.kernel2 import build_layout2
        from jaxmc.sem.enumerate import enumerate_init
        inits = enumerate_init(pcal_model.init, pcal_model.ctx(),
                               pcal_model.vars)
        lay, _ = build_layout2(pcal_model, inits, Bounds())
        for st in inits[:10]:
            row = lay.encode(st)
            back = lay.decode(row)
            assert back == st

    @needs_reference
    def test_grounding_labels(self, pcal_model):
        from jaxmc.compile.ground import ground_actions
        gas = ground_actions(pcal_model)
        labels = {g.label for g in gas}
        assert any(l.startswith("Transfer(") for l in labels)
        assert "Terminating" in labels


class TestDeviceBFS:
    @needs_reference
    def test_atomic_add_counts(self):
        from jaxmc.backend.bfs import TpuExplorer
        model = load(os.path.join(REFERENCE, "atomic_add.tla"))
        r = TpuExplorer(model).run()
        assert r.ok and r.distinct == 5 and r.generated == 7

    @needs_reference
    def test_pcal_intro_matches_interp(self, pcal_model):
        from jaxmc.backend.bfs import TpuExplorer
        r = TpuExplorer(pcal_model).run()
        assert r.ok
        assert r.distinct == 3800     # == interpreter == oracle counts
        assert r.generated == 5850

    def test_buggy_assert_found_with_trace(self):
        from jaxmc.backend.bfs import TpuExplorer
        model = load(os.path.join(SPECS, "pcal_intro_buggy.tla"))
        r = TpuExplorer(model).run()
        assert not r.ok and r.violation.kind == "assert"
        assert len(r.violation.trace) == 6  # same depth as TLC's trace
        # the trace must be a genuine behavior: replay it on the interpreter
        from jaxmc.sem.enumerate import enumerate_init, enumerate_next
        ctx = model.ctx()
        inits = enumerate_init(model.init, ctx, model.vars)
        assert r.violation.trace[0][0] in inits
        for (st, _), (succ, _) in zip(r.violation.trace,
                                      r.violation.trace[1:]):
            succs = []
            try:
                for s2, _lbl in enumerate_next(model.next, ctx, model.vars,
                                               st):
                    succs.append(s2)
            except Exception:
                pass  # assert may fire during full expansion
            assert succ in succs

    def test_invariant_violation(self):
        from jaxmc.backend.bfs import TpuExplorer
        cfg = ModelConfig(specification="Spec",
                          invariants=["MoneyInvariant"])
        model = load(os.path.join(SPECS, "pcal_intro_buggy.tla"), cfg)
        r = TpuExplorer(model).run()
        assert not r.ok and r.violation.kind == "invariant"
        assert r.violation.name == "MoneyInvariant"
        # violating state really violates it
        st = r.violation.trace[-1][0]
        assert st["alice_account"] + st["bob_account"] != st["account_total"]


def _replay_trace(model, trace):
    """The trace must be a genuine behavior: its head an initial state,
    every step an enabled transition (interpreter replay)."""
    from jaxmc.sem.enumerate import enumerate_init, enumerate_next
    ctx = model.ctx()
    inits = enumerate_init(model.init, ctx, model.vars)
    assert trace[0][0] in inits
    for (st, _), (succ, _) in zip(trace, trace[1:]):
        succs = []
        try:
            for s2, _lbl in enumerate_next(model.next, ctx, model.vars,
                                           st):
                succs.append(s2)
        except Exception:
            pass  # assert may fire during full expansion
        assert succ in succs


class TestMesh:
    @needs_reference
    def test_pcal_intro_mesh_counts(self, pcal_model):
        import jax
        from jaxmc.backend.mesh import MeshExplorer
        assert len(jax.devices()) >= 8
        r = MeshExplorer(pcal_model).run()
        assert r.ok
        assert r.distinct == 3800
        assert r.generated == 5850

    @needs_reference
    def test_atomic_add_mesh(self):
        from jaxmc.backend.mesh import MeshExplorer
        model = load(os.path.join(REFERENCE, "atomic_add.tla"))
        r = MeshExplorer(model).run()
        assert r.ok and r.distinct == 5 and r.generated == 7

    # ---- mesh parity (VERDICT r2 #5): traces, named violations,
    # checkpoint/resume ----

    def test_mesh_assert_violation_trace_replays(self):
        from jaxmc.backend.mesh import MeshExplorer
        model = load(os.path.join(SPECS, "pcal_intro_buggy.tla"))
        r = MeshExplorer(model).run()
        assert not r.ok and r.violation.kind == "assert"
        # mesh BFS finds a shortest-path trace with action provenance
        assert len(r.violation.trace) == 6  # TLC's depth
        assert r.violation.trace[-1][1] != "Initial predicate"
        _replay_trace(model, r.violation.trace)

    def test_mesh_invariant_violation_named_with_trace(self):
        from jaxmc.backend.mesh import MeshExplorer
        cfg = ModelConfig(specification="Spec",
                          invariants=["MoneyInvariant"])
        model = load(os.path.join(SPECS, "pcal_intro_buggy.tla"), cfg)
        r = MeshExplorer(model).run()
        assert not r.ok and r.violation.kind == "invariant"
        assert r.violation.name == "MoneyInvariant"  # NAMED (r2: generic)
        st = r.violation.trace[-1][0]
        assert st["alice_account"] + st["bob_account"] != \
            st["account_total"]
        _replay_trace(model, r.violation.trace)

    @needs_reference
    def test_mesh_checkpoint_resume_exact(self, pcal_model, tmp_path):
        from jaxmc.backend.mesh import MeshExplorer
        ck = str(tmp_path / "mesh.ck")
        r1 = MeshExplorer(pcal_model, max_states=1000,
                          checkpoint_path=ck, checkpoint_every=0).run()
        assert r1.truncated and os.path.exists(ck)
        r2 = MeshExplorer(pcal_model, resume_from=ck).run()
        assert r2.ok
        # resumed full-run counts match the direct full run exactly
        assert r2.distinct == 3800 and r2.generated == 5850

    @needs_reference
    def test_mesh_a2a_exchange_counts_and_trace(self, pcal_model):
        # hash-routed all_to_all exchange (SURVEY §2.3 comm rows): same
        # exact counts as the all_gather path, provenance intact through
        # the routed src-index lane
        from jaxmc.backend.mesh import MeshExplorer
        r = MeshExplorer(pcal_model, exchange="a2a").run()
        assert r.ok
        assert r.distinct == 3800 and r.generated == 5850
        model = load(os.path.join(SPECS, "pcal_intro_buggy.tla"))
        r2 = MeshExplorer(model, exchange="a2a").run()
        assert not r2.ok and r2.violation.kind == "assert"
        assert len(r2.violation.trace) == 6
        _replay_trace(model, r2.violation.trace)

    @needs_reference
    def test_mesh_a2a_bucket_overflow_grows_gamma(self, pcal_model):
        # force a tiny capacity factor: the first level must overflow
        # the per-peer bucket, double gamma (possibly repeatedly), and
        # still finish with EXACT counts
        from jaxmc.backend.mesh import MeshExplorer
        ex = MeshExplorer(pcal_model, exchange="a2a")
        ex._a2a_gamma = 0.05
        r = ex.run()
        assert r.ok
        assert r.distinct == 3800 and r.generated == 5850
        assert ex._a2a_gamma > 0.05  # growth actually happened

    def test_mesh_deadlock_trace(self, tmp_path):
        from jaxmc.backend.mesh import MeshExplorer
        spec = tmp_path / "countdown.tla"
        spec.write_text("""---- MODULE countdown ----
EXTENDS Naturals
VARIABLE n
Init == n = 3
Next == n > 0 /\\ n' = n - 1
Spec == Init /\\ [][Next]_n
====""")
        model = load(str(spec))
        r = MeshExplorer(model).run()
        assert not r.ok and r.violation.kind == "deadlock"
        # deadlocked at n=0, depth 3: full provenance trace
        assert len(r.violation.trace) == 4
        assert r.violation.trace[-1][0]["n"] == 0
        _replay_trace(model, r.violation.trace)


class TestGraftEntry:
    @needs_reference
    def test_entry_compiles(self):
        import sys
        sys.path.insert(0, os.path.dirname(SPECS))
        import importlib
        import __graft_entry__ as g
        importlib.reload(g)
        import jax
        fn, args = g.entry()
        en, succ = jax.jit(fn)(*args)
        assert en.shape[1] == args[0].shape[0]
        assert succ.shape[-1] == args[0].shape[1]

    @needs_reference
    def test_dryrun_multichip(self):
        import sys
        sys.path.insert(0, os.path.dirname(SPECS))
        import __graft_entry__ as g
        g.dryrun_multichip(8)


class TestHostSeen:
    @needs_reference
    def test_host_seen_exact_counts(self):
        from jaxmc import native_store
        if not native_store.is_available():
            import pytest
            pytest.skip("no native toolchain")
        from jaxmc.backend.bfs import TpuExplorer
        cfg = parse_cfg(open(os.path.join(REFERENCE, "pcal_intro.cfg")).read())
        model = load(os.path.join(REFERENCE, "pcal_intro.tla"), cfg)
        r = TpuExplorer(model, host_seen=True).run()
        assert r.ok and r.distinct == 3800 and r.generated == 5850

    def test_host_seen_finds_violation_with_trace(self):
        from jaxmc import native_store
        if not native_store.is_available():
            import pytest
            pytest.skip("no native toolchain")
        from jaxmc.backend.bfs import TpuExplorer
        model = load(os.path.join(SPECS, "pcal_intro_buggy.tla"))
        r = TpuExplorer(model, host_seen=True).run()
        assert not r.ok and r.violation.kind == "assert"
        assert len(r.violation.trace) >= 2


class TestDeviceSymmetry:
    # cfg SYMMETRY on the device backends (VERDICT r1 #7): rows are
    # canonicalized to orbit representatives before fingerprinting
    # (compile/symmetry2.py), so device counts equal the interp's
    # symmetry-reduced counts

    def test_symtoy_reduced_counts_match_interp(self):
        from jaxmc.engine.explore import Explorer
        from jaxmc.backend.bfs import TpuExplorer
        cfg = parse_cfg(open(os.path.join(SPECS, "symtoy.cfg")).read())
        cfg.check_deadlock = False
        model = load(os.path.join(SPECS, "symtoy.tla"), cfg)
        ri = Explorer(model).run()
        ex = TpuExplorer(model)
        assert ex.canon_fn is not None
        rj = ex.run()
        assert ri.ok and rj.ok
        # symmetry-reduced (unreduced would be 109/81)
        assert (ri.generated, ri.distinct) == (33, 22)
        assert (rj.generated, rj.distinct) == (33, 22)
        assert not rj.warnings  # reduction applied: no SYMMETRY warning

    def test_multiinit_orbit_dedup_matches_interp(self):
        # advisor r2 high: with `Init == owner \in P` the |P| raw init
        # states share one orbit; _prepare_init must dedup them by
        # canonical representative or device counts inflate (and seen is
        # seeded with duplicate canonical fingerprints)
        from jaxmc.engine.explore import Explorer
        from jaxmc.backend.bfs import TpuExplorer
        cfg = parse_cfg(
            open(os.path.join(SPECS, "symtoy_multiinit.cfg")).read())
        cfg.check_deadlock = False
        model = load(os.path.join(SPECS, "symtoy_multiinit.tla"), cfg)
        ri = Explorer(model).run()
        assert ri.ok
        ex = TpuExplorer(model)
        assert ex.canon_fn is not None
        rj = ex.run()
        exr = TpuExplorer(model, resident=True)
        rr = exr.run()
        assert rj.ok and rr.ok
        assert (rj.generated, rj.distinct) == (ri.generated, ri.distinct)
        assert (rr.generated, rr.distinct) == (ri.generated, ri.distinct)

    @pytest.mark.slow
    def test_mcvoting_reduced_counts_match_interp(self):
        # the corpus's symmetry workhorse (MCPaxos's symmetry is the
        # identity over its singleton sets): growset-of-records lanes
        # exercise the element-remap + segment re-sort transform
        from jaxmc.backend.bfs import TpuExplorer
        d = os.path.join(REFERENCE, "examples", "Paxos")
        cfg = parse_cfg(open(os.path.join(d, "MCVoting.cfg")).read())
        cfg.check_deadlock = False
        model = load(os.path.join(d, "MCVoting.tla"), cfg)
        ex = TpuExplorer(model)
        assert ex.canon_fn is not None
        r = ex.run()
        assert r.ok
        assert (r.generated, r.distinct) == (406, 77)  # interp pin


class TestDeviceCheckpoint:
    # checkpoint/resume on the device backends (VERDICT r1 #7): every
    # device mode checkpoints at level/dispatch boundaries and a resumed
    # run must finish with IDENTICAL full-run counts and verdicts

    def _pcal(self):
        cfg = parse_cfg(open(os.path.join(REFERENCE,
                                          "pcal_intro.cfg")).read())
        return load(os.path.join(REFERENCE, "pcal_intro.tla"), cfg)

    @needs_reference
    def test_level_mode_resume_exact(self, tmp_path):
        from jaxmc.backend.bfs import TpuExplorer
        ckp = str(tmp_path / "ck.pkl")
        model = self._pcal()
        r1 = TpuExplorer(model, checkpoint_path=ckp,
                         checkpoint_every=0.0).run()
        assert r1.ok and (r1.generated, r1.distinct) == (5850, 3800)
        assert os.path.exists(ckp)
        r2 = TpuExplorer(model, resume_from=ckp).run()
        assert r2.ok
        assert (r2.generated, r2.distinct) == (5850, 3800)
        assert r2.diameter == r1.diameter

    def test_level_mode_resume_finds_violation_with_trace(self, tmp_path):
        from jaxmc.backend.bfs import TpuExplorer
        ckp = str(tmp_path / "ck.pkl")
        model = load(os.path.join(SPECS, "pcal_intro_buggy.tla"))
        r1 = TpuExplorer(model, checkpoint_path=ckp,
                         checkpoint_every=0.0).run()
        assert not r1.ok and os.path.exists(ckp)
        r2 = TpuExplorer(model, resume_from=ckp).run()
        assert not r2.ok and r2.violation.kind == r1.violation.kind
        # the restored trace levels still reconstruct a full trace
        assert len(r2.violation.trace) >= 2

    @needs_reference
    def test_host_seen_resume_exact(self, tmp_path):
        from jaxmc import native_store
        if not native_store.is_available():
            pytest.skip("no native toolchain")
        from jaxmc.backend.bfs import TpuExplorer
        ckp = str(tmp_path / "ck.pkl")
        model = self._pcal()
        r1 = TpuExplorer(model, host_seen=True, checkpoint_path=ckp,
                         checkpoint_every=0.0).run()
        assert r1.ok and os.path.exists(ckp)
        r2 = TpuExplorer(model, host_seen=True, resume_from=ckp).run()
        assert r2.ok
        assert (r2.generated, r2.distinct) == (5850, 3800)

    @needs_reference
    def test_resident_resume_exact(self, tmp_path):
        from jaxmc.backend.bfs import TpuExplorer
        ckp = str(tmp_path / "ck.pkl")
        model = self._pcal()
        ex = TpuExplorer(model, resident=True, chunk=256,
                         checkpoint_path=ckp, checkpoint_every=0.0)
        ex._res_maxlvl = 1  # checkpoint between every level
        r1 = ex.run()
        assert r1.ok and os.path.exists(ckp)
        ex2 = TpuExplorer(model, resident=True, chunk=256,
                          resume_from=ckp)
        ex2._res_maxlvl = 1
        r2 = ex2.run()
        assert r2.ok
        assert (r2.generated, r2.distinct) == (5850, 3800)

    @needs_reference
    def test_resume_mode_mismatch_rejected(self, tmp_path):
        from jaxmc.backend.bfs import TpuExplorer
        ckp = str(tmp_path / "ck.pkl")
        model = self._pcal()
        TpuExplorer(model, checkpoint_path=ckp,
                    checkpoint_every=0.0).run()
        with pytest.raises(ValueError, match="device mode"):
            TpuExplorer(model, resident=True, resume_from=ckp).run()


class TestResident:
    # resident mode: the whole BFS inside one jitted while_loop
    # (backend/bfs.py _run_resident) — zero host syncs per level;
    # counts must still match the interpreter exactly

    @staticmethod
    def _raft_micro():
        ldr = Loader([os.path.join(REFERENCE, "examples"), SPECS])
        return bind_model(
            ldr.load_path(os.path.join(SPECS, "MCraftMicro.tla")),
            parse_cfg(open(os.path.join(SPECS, "MCraft_micro.cfg")).read()))

    @needs_reference
    def test_raft_micro_exact_counts_and_truncation(self):
        # flagship workload at the scale that completes (pinned 6185/694
        # in test_kernel2 for interp/host_seen); small chunk exercises
        # the multi-chunk accumulator path
        from jaxmc.backend.bfs import TpuExplorer
        ex = TpuExplorer(self._raft_micro(), resident=True, chunk=128)
        r = ex.run()
        assert r.ok
        assert (r.generated, r.distinct) == (6185, 694)

        # truncation at a state limit (same instance: jit cache reused)
        ex.max_states = 100
        r2 = ex.run()
        assert r2.ok and r2.truncated and r2.distinct >= 100

    @pytest.mark.slow
    def test_resident_growth_redo_exactness(self):
        # tiny starting caps force every grow-and-redo status (each
        # growth recompiles, hence slow-marked); counts stay exact
        from jaxmc.backend.bfs import TpuExplorer
        ex = TpuExplorer(self._raft_micro(), resident=True, chunk=128)
        ex._res_caps = {"SC": 1 << 8, "FCap": 128, "AccCap": 1 << 9,
                        "VC": 1 << 8}
        r = ex.run()
        assert r.ok
        assert (r.generated, r.distinct) == (6185, 694)
        # capacities were learned by growth during the run
        assert ex._res_caps["SC"] >= 1024

    def test_resident_deadlock_depth_matches_interp(self, tmp_path):
        # deadlock states live in the CURRENT frontier: resident must
        # report the same diameter as the interp backend (regression:
        # the level loop used to advance depth before exiting)
        from jaxmc.engine.explore import Explorer
        from jaxmc.backend.bfs import TpuExplorer
        spec = tmp_path / "cnt.tla"
        spec.write_text("""---- MODULE cnt ----
EXTENDS Naturals
VARIABLE x
Init == x = 0
Next == x < 2 /\\ x' = x + 1
Spec == Init /\\ [][Next]_x
====
""")
        model = load(str(spec), ModelConfig(specification="Spec"))
        ri = Explorer(model).run()
        rr = TpuExplorer(model, resident=True).run()
        assert not ri.ok and not rr.ok
        assert ri.violation.kind == rr.violation.kind == "deadlock"
        assert ri.diameter == rr.diameter

    @needs_reference
    def test_resident_rejects_host_seen_combo(self):
        # mutually exclusive seen-set homes: must be diagnosed up front,
        # not silently resolved in favor of one mode
        from jaxmc.compile.vspec import CompileError
        from jaxmc.backend.bfs import TpuExplorer
        with pytest.raises(CompileError, match="mutually exclusive"):
            TpuExplorer(self._raft_micro(), resident=True, host_seen=True)

    @needs_reference
    def test_resident_rejects_temporal_models(self):
        from jaxmc.compile.vspec import CompileError
        from jaxmc.backend.bfs import TpuExplorer
        path = os.path.join(REFERENCE, "examples", "SpecifyingSystems",
                            "HourClock", "HourClock2.tla")
        cfg = parse_cfg(open(os.path.join(
            REFERENCE, "examples", "SpecifyingSystems", "HourClock",
            "HourClock2.cfg")).read())
        model = load(path, cfg)
        with pytest.raises(CompileError):
            TpuExplorer(model, resident=True)


class TestCorpusOnDevice:
    # seq-heavy corpus models must reproduce the interpreter's exact
    # counts on the device backend (tuple messages, Tail, Lose's dynamic
    # sequence surgery, record-set TypeInvariants)
    CASES = [
        ("examples/SpecifyingSystems/FIFO/MCInnerFIFO.tla", 3864, 9660),
        ("examples/SpecifyingSystems/TLC/MCAlternatingBit.tla", 240, 1392),
    ]

    @pytest.mark.parametrize("rel,distinct,generated", CASES,
                             ids=[c[0].split("/")[-1] for c in CASES])
    @needs_reference
    def test_corpus_model_exact(self, rel, distinct, generated):
        from jaxmc import native_store
        if not native_store.is_available():
            pytest.skip("no native toolchain")
        from jaxmc.backend.bfs import TpuExplorer
        spec = os.path.join(REFERENCE, rel)
        cfg = parse_cfg(open(spec[:-4] + ".cfg", encoding="utf-8",
                             errors="replace").read())
        model = load(spec, cfg)
        r = TpuExplorer(model, host_seen=True, store_trace=False).run()
        assert r.ok
        assert r.distinct == distinct
        assert r.generated == generated


class TestRefinementOnDevice:
    # refinement PROPERTYs check stepwise on the jax backend too (host-
    # side over the streamed candidate edges) — verdict parity with interp

    @needs_reference
    def test_hourclock2_equivalence_checked(self):
        from jaxmc.backend.bfs import TpuExplorer
        d = os.path.join(REFERENCE, "examples/SpecifyingSystems/HourClock")
        cfg = parse_cfg(open(os.path.join(d, "HourClock2.cfg")).read())
        model = load(os.path.join(d, "HourClock2.tla"), cfg)
        r = TpuExplorer(model).run()
        assert r.ok
        assert r.distinct == 12 and r.generated == 24
        assert not any("HC2" in w for w in r.warnings)

    @needs_reference
    def test_alternating_bit_abcspec_checked(self):
        from jaxmc.backend.bfs import TpuExplorer
        d = os.path.join(REFERENCE, "examples/SpecifyingSystems/TLC")
        cfg = parse_cfg(open(os.path.join(d, "MCAlternatingBit.cfg")).read())
        model = load(os.path.join(d, "MCAlternatingBit.tla"), cfg)
        r = TpuExplorer(model).run()
        assert r.ok
        assert r.distinct == 240 and r.generated == 1392
        # r3: ABCSpec's ABCFairness half is checked over the streamed
        # behavior graph too — no "NOT checked" warning remains
        assert not any("NOT checked" in w for w in r.warnings), r.warnings

    def test_non_refinement_detected(self, tmp_path):
        from jaxmc.backend.bfs import TpuExplorer
        spec = tmp_path / "badhc.tla"
        spec.write_text("""---- MODULE badhc ----
EXTENDS Naturals
VARIABLE hr
HCini == hr \\in 1..12
HCnxt == hr' = IF hr >= 11 THEN 1 ELSE hr + 2
HC == HCini /\\ [][HCnxt]_hr
Jump == hr' = IF hr = 12 THEN 1 ELSE hr + 1
JumpSpec == HCini /\\ [][Jump]_hr
====
""")
        cfg = ModelConfig(specification="HC", properties=["JumpSpec"],
                          check_deadlock=False)
        model = load(str(spec), cfg)
        r = TpuExplorer(model).run()
        assert not r.ok
        assert r.violation.kind == "property"
        assert r.violation.name == "JumpSpec"
        # the trace ends with the non-refining step
        assert len(r.violation.trace) >= 2


class TestLevelRankMergeParity:
    """The level engine against the INTERPRETER
    (jaxmc.engine.explore.Explorer, the semantic reference of every
    engine) on PROPERTY cfgs.  The level mode is the host loop
    refinement and temporal PROPERTY checking runs on; it merges each
    level's candidates into the sorted seen prefix by rank
    (bfs._rank_merge), and the edge stream the checkers read follows
    the frontier that merge produces.  Counts, diameter and verdict
    must equal the interpreter's on passing cfgs; verdict, violated
    property and the whole counterexample trace on failing ones
    (`generated` there is an early-exit count and is not compared)."""

    REFINE_OK = """---- MODULE rmhc ----
EXTENDS Naturals
VARIABLE hr
HCini == hr \\in 1..12
HCnxt == hr' = IF hr = 12 THEN 1 ELSE hr + 1
HC == HCini /\\ [][HCnxt]_hr
====
"""
    REFINE_BAD = """---- MODULE rmbad ----
EXTENDS Naturals
VARIABLE hr
HCini == hr \\in 1..12
HCnxt == hr' = IF hr >= 11 THEN 1 ELSE hr + 2
HC == HCini /\\ [][HCnxt]_hr
Jump == hr' = IF hr = 12 THEN 1 ELSE hr + 1
JumpSpec == HCini /\\ [][Jump]_hr
====
"""
    TEMPORAL = """---- MODULE rmlive ----
EXTENDS Naturals
VARIABLE hr
Init == hr \\in 1..4
Next == hr' = (hr %% 12) + 1
Spec == Init /\\ [][Next]_hr /\\ WF_hr(Next)
Cycles == []<><<Next>>_hr
====
""".replace("%%", "%")

    def _pair(self, tmp_path, name, text, cfg):
        """(level engine, interpreter) results on one inline spec."""
        from jaxmc.engine.explore import Explorer
        from jaxmc.backend.bfs import TpuExplorer
        p = tmp_path / name
        p.write_text(text)
        return (TpuExplorer(load(str(p), cfg)).run(),
                Explorer(load(str(p), cfg)).run())

    @staticmethod
    def _same_passing_run(dev, ref):
        assert dev.ok and ref.ok
        assert (dev.distinct, dev.generated, dev.diameter) == \
            (ref.distinct, ref.generated, ref.diameter)

    @staticmethod
    def _same_counterexample(dev, ref):
        assert not dev.ok and not ref.ok
        assert dev.violation.kind == ref.violation.kind
        assert dev.violation.name == ref.violation.name
        # the whole trace: same states, same action labels
        assert dev.violation.trace == ref.violation.trace

    def test_refinement_counts_identical(self, tmp_path):
        """Reference: the interpreter."""
        dev, ref = self._pair(
            tmp_path, "rmhc.tla", self.REFINE_OK,
            ModelConfig(specification="HC", properties=["HC"],
                        check_deadlock=False))
        self._same_passing_run(dev, ref)

    def test_refinement_violation_trace_identical(self, tmp_path):
        """Reference: the interpreter."""
        dev, ref = self._pair(
            tmp_path, "rmbad.tla", self.REFINE_BAD,
            ModelConfig(specification="HC", properties=["JumpSpec"],
                        check_deadlock=False))
        assert dev.violation.name == "JumpSpec"
        self._same_counterexample(dev, ref)

    def test_temporal_counts_identical(self, tmp_path):
        """Reference: the interpreter.  The behavior-graph liveness
        path streams every level's edges through the merged frontier
        the rank merge produces."""
        dev, ref = self._pair(
            tmp_path, "rmlive.tla", self.TEMPORAL,
            ModelConfig(specification="Spec", properties=["Cycles"],
                        check_deadlock=False))
        self._same_passing_run(dev, ref)

    def test_temporal_violation_parity(self, tmp_path):
        """Reference: the interpreter.  Without fairness the cycle
        property fails: same verdict, same counterexample."""
        dev, ref = self._pair(
            tmp_path, "rmlive.tla", self.TEMPORAL,
            ModelConfig(init="Init", next="Next",
                        properties=["Cycles"], check_deadlock=False))
        self._same_counterexample(dev, ref)


@pytest.mark.slow
def test_mesh_raft_micro_counts():
    # the flagship wide-state workload shards: MCraftMicro on an 8-device
    # mesh matches the interp/single-chip counts exactly
    import jax
    from jaxmc.backend.mesh import MeshExplorer
    assert len(jax.devices()) >= 8
    ldr = Loader([os.path.join(REFERENCE, "examples"), SPECS])
    model = bind_model(
        ldr.load_path(os.path.join(SPECS, "MCraftMicro.tla")),
        parse_cfg(open(os.path.join(SPECS, "MCraft_micro.cfg")).read()))
    r = MeshExplorer(model).run()
    assert r.ok
    assert r.distinct == 694 and r.generated == 6185


@needs_reference
def test_mesh_innerfifo_counts():
    # mesh-vs-interp equality on a corpus model with constraints and a
    # canonically-sorted container (the fp128-key dedup path)
    import jax
    from jaxmc.backend.mesh import MeshExplorer
    assert len(jax.devices()) >= 8
    d = os.path.join(REFERENCE, "examples/SpecifyingSystems/FIFO")
    cfg = parse_cfg(open(os.path.join(d, "MCInnerFIFO.cfg")).read())
    model = load(os.path.join(d, "MCInnerFIFO.tla"), cfg)
    r = MeshExplorer(model).run()
    assert r.ok
    assert r.distinct == 3864 and r.generated == 9660


class TestHybrid:
    """Hybrid execution (VERDICT r3 #2): uncompilable actions,
    invariants, or constraints demote to the exact interpreter inside
    the host_seen device mode instead of rejecting the whole spec."""

    @needs_reference
    def test_consensus_invariant_fallback_counts(self):
        # MCConsensus's Inv uses IsFiniteSet (uncompilable): the
        # invariant demotes to host evaluation over decoded rows while
        # the actions stay compiled; counts match the interp pin
        from jaxmc.backend.bfs import TpuExplorer
        d = os.path.join(REFERENCE, "examples/Paxos")
        cfg = parse_cfg(open(os.path.join(d, "MCConsensus.cfg")).read())
        cfg.check_deadlock = False
        model = load(os.path.join(d, "MCConsensus.tla"), cfg)
        ex = TpuExplorer(model, store_trace=True, host_seen=True)
        assert [nm for nm, _, _ in ex.fb_invs] == ["Inv"]
        assert not ex.fb_arms
        r = ex.run()
        assert r.ok and (r.generated, r.distinct) == (7, 4)

    @needs_reference
    def test_asynch_interface_action_fallback_counts(self):
        # AsynchInterface's Send leaves val' nondeterministic (val' \in
        # Data): that arm demotes to interpreter enumeration, Rcv stays
        # compiled; counts match the interp pin
        from jaxmc.backend.bfs import TpuExplorer
        d = os.path.join(REFERENCE,
                         "examples/SpecifyingSystems/AsynchronousInterface")
        cfg = parse_cfg(open(os.path.join(d, "AsynchInterface.cfg")).read())
        model = load(os.path.join(d, "AsynchInterface.tla"), cfg)
        ex = TpuExplorer(model, store_trace=True, host_seen=True)
        assert [a.label for a, _ in ex.fb_arms] == ["Send"]
        r = ex.run()
        assert r.ok and (r.generated, r.distinct) == (30, 12)

    @needs_reference
    def test_hybrid_requires_host_seen(self):
        # level mode cannot interleave interpreter work: a spec that
        # needs hybrid execution is rejected with a MODE error (fix is
        # a flag, not a different backend)
        from jaxmc.backend.bfs import TpuExplorer
        from jaxmc.compile.vspec import ModeError
        d = os.path.join(REFERENCE, "examples/Paxos")
        cfg = parse_cfg(open(os.path.join(d, "MCConsensus.cfg")).read())
        cfg.check_deadlock = False
        model = load(os.path.join(d, "MCConsensus.tla"), cfg)
        with pytest.raises(ModeError, match="hybrid"):
            TpuExplorer(model, store_trace=True, host_seen=False)

    @pytest.mark.slow
    def test_paxos_demoted_guard_restart_counts(self):
        # MCPaxos Phase2a's Q1bv guard compiles only via conjunct
        # demotion (False + abort flag); the abort fires on a reachable
        # state, the engine demotes those arms to the interpreter,
        # restarts, and the counts match the interp pin exactly
        from jaxmc.backend.bfs import TpuExplorer
        d = os.path.join(REFERENCE, "examples/Paxos")
        cfg = parse_cfg(open(os.path.join(d, "MCPaxos.cfg")).read())
        model = load(os.path.join(d, "MCPaxos.tla"), cfg)
        ex = TpuExplorer(model, store_trace=True, host_seen=True)
        assert ex._demotable  # Phase2a arms carry demoted guards
        r = ex.run()
        assert r.ok and (r.generated, r.distinct) == (82, 25)
        assert any("Phase2a" in a.label for a, _ in ex.fb_arms)

    @pytest.mark.slow
    def test_ssi_small_full_arm_fallback_counts(self):
        # the SSI envelope model: EVERY action arm demotes (recursion/
        # CHOOSE-heavy), so the device contributes hashing/dedup while
        # the interpreter enumerates — first SI-class workload running
        # through the device engine, counts exact
        from jaxmc.backend.bfs import TpuExplorer
        ldr = Loader([os.path.join(REFERENCE, "examples"), SPECS])
        model = bind_model(
            ldr.load_path(os.path.join(SPECS, "MCserializableSI.tla")),
            parse_cfg(open(os.path.join(
                SPECS, "MCserializableSI_small.cfg")).read()))
        ex = TpuExplorer(model, store_trace=True, host_seen=True)
        assert ex.fb_arms
        r = ex.run()
        assert r.ok and (r.generated, r.distinct) == (945, 569)


class TestScalarUnions:
    """Scalar variants in the union lane encoding (VERDICT r3 #3): the
    CachingMemory shape — buf[p] holds NoVal (enum) or a request record
    — encodes as a tagged union with $scalar variants."""

    def test_scalar_union_encode_roundtrip(self):
        # fast pure-vspec coverage: NoVal (enum) and a request record
        # share one tagged union; encode/decode roundtrips both and the
        # merge error still names the OBSERVED kinds
        from jaxmc.compile.vspec import (CompileError, EnumUniverse,
                                         decode, encode, infer, merge)
        from jaxmc.sem.values import Fcn, ModelValue
        uni = EnumUniverse()
        nv = ModelValue("NoVal")
        rec = Fcn({"adr": ModelValue("a1"), "op": "Rd", "val": 3})
        u = merge(infer(nv, uni), infer(rec, uni))
        assert u.kind == "union" and len(u.variants) == 2
        for v in (nv, rec):
            out = []
            encode(v, u, uni, out)
            assert len(out) == u.width
            back, _ = decode(out, 0, u, uni)
            assert back == v and (isinstance(back, bool)
                                  == isinstance(v, bool))
        with pytest.raises(CompileError, match="enum and seq"):
            merge(infer(nv, uni), infer(Fcn({1: 5, 2: 6}), uni))

    @pytest.mark.slow
    def test_internal_memory_counts(self):
        # previously rejected with "cannot merge shapes enum and fcn";
        # Req/Rsp arms demote (memInt' nondeterminism via Send/Reply),
        # Do(p) stays compiled
        from jaxmc.backend.bfs import TpuExplorer
        d = os.path.join(REFERENCE,
                         "examples/SpecifyingSystems/CachingMemory")
        cfg = parse_cfg(open(os.path.join(d,
                                          "MCInternalMemory.cfg")).read())
        model = load(os.path.join(d, "MCInternalMemory.tla"), cfg)
        r = TpuExplorer(model, store_trace=False, host_seen=True).run()
        assert r.ok and (r.generated, r.distinct) == (21400, 4408)

    @pytest.mark.slow
    def test_golden_inner_serial_device_run(self):
        # THE golden run: the corpus's only captured full TLC output
        # (testout2:265-266 — TLC 1.57 took 22 hours) reproduced on the
        # device backend: 6181 generated / 195 distinct, diameter 5
        from jaxmc.backend.bfs import TpuExplorer
        d = os.path.join(REFERENCE,
                         "examples/SpecifyingSystems/AdvancedExamples")
        cfg = parse_cfg(open(os.path.join(d, "MCInnerSerial.cfg")).read())
        model = load(os.path.join(d, "MCInnerSerial.tla"), cfg)
        r = TpuExplorer(model, store_trace=False, host_seen=True).run()
        assert r.ok and (r.generated, r.distinct) == (6181, 195)

    @pytest.mark.slow
    def test_live_write_through_cache_device_run(self):
        # liveness PROPERTIES check through the hybrid edge stream on a
        # scalar-union model: LM_Inner_LISpec + LM_Inner_Liveness verify
        # with no "NOT checked" warnings beyond the host_seen note
        from jaxmc.backend.bfs import TpuExplorer
        d = os.path.join(REFERENCE, "examples/SpecifyingSystems/Liveness")
        cfg = parse_cfg(open(os.path.join(
            d, "MCLiveWriteThroughCache.cfg")).read())
        model = load(os.path.join(d, "MCLiveWriteThroughCache.tla"), cfg)
        r = TpuExplorer(model, store_trace=True, host_seen=True).run()
        assert r.ok and (r.generated, r.distinct) == (28170, 5196)
        assert not [w for w in r.warnings if "NOT checked" in w]


@pytest.mark.slow
def test_multihost_dcn_dryrun():
    # the DCN layer (SURVEY §2.3/§5 distributed comm backend): 2 jax
    # PROCESSES x 4 virtual CPU devices, jax.distributed.initialize with
    # a localhost coordinator, collectives crossing process boundaries
    # (Gloo on CPU; same program rides ICI/DCN on a pod). Full
    # MCraftMicro with exact counts on every process.
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "__graft_entry__", os.path.join(
            os.path.dirname(SPECS), "__graft_entry__.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.dryrun_multihost(num_processes=2, local_devices=4)


@pytest.mark.slow
def test_mcraft_3s_mid4_completes_exhaustively():
    # The MCraft_3s ladder's first completed rung (VERDICT r4 #2):
    # reference raft.tla with Server={s1,s2,s3}, MaxMsgDomain 4
    # (specs/MCraft_3s_mid4.cfg — one step below the BASELINE model of
    # record). First measured completion: 11,883,463 generated /
    # 714,286 distinct, no violation, via the per-arm-granular hybrid
    # with strided adaptive relayout (one relayout recovered the
    # message variant the sampler missed). ~46 min on the contended
    # 1-core dev box at 6.6k st/s steady state.
    from jaxmc.backend.bfs import TpuExplorer
    ldr = Loader([os.path.join(REFERENCE, "examples"), SPECS])
    model = bind_model(
        ldr.load_path(os.path.join(SPECS, "MCraftMicro.tla")),
        parse_cfg(open(os.path.join(SPECS, "MCraft_3s_mid4.cfg")).read()))
    ex = TpuExplorer(model, store_trace=False, host_seen=True,
                     sample_cfg=(3000, 200, 100))
    r = ex.run()
    assert r.ok
    assert (r.generated, r.distinct) == (11883463, 714286)


@pytest.mark.slow
def test_multihost_trace_parity(tmp_path):
    # VERDICT r4 #7: a violating model on the 2x4 multi-host dryrun must
    # reproduce the EXACT single-chip counterexample trace. The child
    # processes record only their own frontier/provenance shards and
    # reassemble the chain with the process_allgather pull protocol;
    # every process prints the same trace, equal line-for-line to the
    # single-process MeshExplorer's over the same 8 global devices.
    import socket
    import subprocess
    import sys as _sys
    import time as _time
    spec = tmp_path / "mhviol.tla"
    spec.write_text("""---- MODULE mhviol ----
EXTENDS Naturals
VARIABLES x, y
Init == x = 0 /\\ y = 0
Next == \\/ x < 6 /\\ x' = x + 1 /\\ UNCHANGED y
        \\/ y < 6 /\\ y' = y + 1 /\\ UNCHANGED x
Inv == x + y < 5
====
""")
    cfgp = tmp_path / "mhviol.cfg"
    cfgp.write_text("INIT Init\nNEXT Next\nINVARIANT Inv\n")

    # single-chip reference: MeshExplorer over this process's 8 virtual
    # devices (same global device count as 2 procs x 4 below)
    from jaxmc.backend.mesh import MeshExplorer
    from jaxmc.backend.multihost import fmt_trace_line
    model = load(str(spec), parse_cfg(cfgp.read_text()))
    r = MeshExplorer(model).run()
    assert not r.ok and r.violation.kind == "invariant"
    assert r.violation.name == "Inv"
    _replay_trace(model, r.violation.trace)
    ref_lines = [fmt_trace_line(i, st, lbl)
                 for i, (st, lbl) in enumerate(r.violation.trace)]

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    repo = os.path.dirname(SPECS)
    procs, logs = [], []
    for pid in range(2):
        env = dict(os.environ, PYTHONPATH=repo)
        env.pop("JAX_PLATFORMS", None)
        log = tmp_path / f"mh{pid}.log"
        logs.append(log)
        procs.append(subprocess.Popen(
            [_sys.executable, "-m", "jaxmc.backend.multihost",
             "--process-id", str(pid), "--num-processes", "2",
             "--coordinator", f"localhost:{port}",
             "--local-devices", "4",
             "--spec", str(spec), "--cfg", str(cfgp)],
            stdout=open(log, "w"), stderr=subprocess.STDOUT,
            text=True, env=env, cwd=repo))
    deadline = _time.time() + 1200
    for p in procs:
        try:
            p.wait(timeout=max(1.0, deadline - _time.time()))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
    per_proc = []
    for pid, log in enumerate(logs):
        text = log.read_text()
        assert procs[pid].returncode == 0, text[-2000:]
        assert "MHVIOLATION" in text, text[-2000:]
        lines = [ln for ln in text.splitlines()
                 if ln.startswith("MHTRACE ")]
        per_proc.append(lines)
    assert per_proc[0] == per_proc[1], "processes disagree on the trace"
    assert per_proc[0] == ref_lines, (
        "multi-host trace differs from the single-chip mesh trace:\n"
        + "\n".join(per_proc[0]) + "\n--- vs ---\n" + "\n".join(ref_lines))


class TestMeshRefinementTemporal:
    """Refinement + temporal PROPERTYs on the MESH backend (VERDICT r3
    #9): the host runs the same stepwise/behavior-graph checkers over
    the streamed exchanged-candidate edges; verdicts match interp."""

    @needs_reference
    def test_mesh_hourclock2_refinement_checked(self):
        from jaxmc.backend.mesh import MeshExplorer
        d = os.path.join(REFERENCE, "examples/SpecifyingSystems/HourClock")
        cfg = parse_cfg(open(os.path.join(d, "HourClock2.cfg")).read())
        model = load(os.path.join(d, "HourClock2.tla"), cfg)
        r = MeshExplorer(model).run()
        assert r.ok and r.distinct == 12 and r.generated == 24
        assert not any("NOT checked" in w for w in r.warnings), r.warnings

    def test_mesh_non_refinement_detected(self, tmp_path):
        from jaxmc.backend.mesh import MeshExplorer
        spec = tmp_path / "badhc.tla"
        spec.write_text("""---- MODULE badhc ----
EXTENDS Naturals
VARIABLE hr
HCini == hr \\in 1..12
HCnxt == hr' = IF hr >= 11 THEN 1 ELSE hr + 2
HC == HCini /\\ [][HCnxt]_hr
Jump == hr' = IF hr = 12 THEN 1 ELSE hr + 1
JumpSpec == HCini /\\ [][Jump]_hr
====
""")
        cfg = ModelConfig(specification="HC", properties=["JumpSpec"],
                          check_deadlock=False)
        model = load(str(spec), cfg)
        r = MeshExplorer(model).run()
        assert not r.ok
        assert r.violation.kind == "property"
        assert r.violation.name == "JumpSpec"
        assert len(r.violation.trace) >= 2

    @pytest.mark.slow
    def test_mesh_alternating_bit_liveness_checked(self):
        # SentLeadsToRcvd (under ABSpec fairness) + ABCSpec refinement
        # verified over the mesh's streamed behavior graph — the exact
        # deliverable model of VERDICT r3 #9
        from jaxmc.backend.mesh import MeshExplorer
        d = os.path.join(REFERENCE, "examples/SpecifyingSystems/TLC")
        cfg = parse_cfg(open(os.path.join(d, "MCAlternatingBit.cfg")).read())
        model = load(os.path.join(d, "MCAlternatingBit.tla"), cfg)
        r = MeshExplorer(model).run()
        assert r.ok and r.distinct == 240 and r.generated == 1392
        assert not any("NOT checked" in w for w in r.warnings), r.warnings


def test_per_arm_demotion_keeps_siblings_compiled(tmp_path):
    # VERDICT r4 #3 (finer demotion granularity): Next has raft's shape
    # /\ (\/ ...actions...) /\ rider (raft.tla:482-493). split_arms now
    # distributes the rider over the disjuncts, so ONE uncompilable
    # action (recursion here) demotes only its own arm — the sibling
    # arms stay compiled — and the hybrid run still matches the
    # interpreter exactly. Before this, the whole conjunction was a
    # single arm and any demotion sent 100% of the model to the interp.
    from jaxmc.backend.bfs import TpuExplorer
    from jaxmc.engine.explore import Explorer
    spec = tmp_path / "armgran.tla"
    spec.write_text("""---- MODULE armgran ----
EXTENDS Naturals
VARIABLES x, h
RECURSIVE Fib(_)
Fib(n) == IF n <= 1 THEN n ELSE Fib(n - 1) + Fib(n - 2)
Init == x = 0 /\\ h = {}
Bump == x < 6 /\\ x' = x + 1
Drop == x > 2 /\\ x' = x - 2
Weird == x = 6 /\\ x' = Fib(x) % 5
Next == /\\ Bump \\/ Drop \\/ Weird
        /\\ h' = h \\cup {x}
====
""")
    cfg = ModelConfig(specification=None, init="Init", next="Next",
                      check_deadlock=False)
    model = load(str(spec), cfg)
    ri = Explorer(model).run()
    assert ri.ok
    ex = TpuExplorer(model, store_trace=False, host_seen=True)
    assert len(ex.fb_arms) == 1, \
        [r for _, r in ex.fb_arms]  # only Weird demotes
    assert ex.A >= 2  # Bump and Drop (with the rider) stay compiled
    assert len(ex.arms) == 3
    r = ex.run()
    assert r.ok
    assert (r.generated, r.distinct) == (ri.generated, ri.distinct)


def test_adaptive_relayout_recovers_unobserved_variant(tmp_path):
    # hybrid adaptive relayout (r4): a value shape the layout sampler
    # never OBSERVED (a record appearing only at depth 10) makes its
    # encode fail mid-search; the engine re-samples from the abort-time
    # frontier, rebuilds the layout with the variant present, restarts,
    # and completes with exact counts — no arm demotion needed
    from jaxmc.backend.bfs import TpuExplorer
    from jaxmc.engine.explore import Explorer
    spec = tmp_path / "deepvar.tla"
    spec.write_text("""---- MODULE deepvar ----
EXTENDS Naturals
VARIABLES x, n
Init == n = 0 /\\ x = "none"
Step == n < 9 /\\ n' = n + 1 /\\ UNCHANGED x
Deep == n = 9 /\\ n' = n /\\ x' = [a |-> n]
Next == Step \\/ Deep
====
""")
    cfg = ModelConfig(specification=None, init="Init", next="Next",
                      check_deadlock=False)
    model = load(str(spec), cfg)
    ri = Explorer(model).run()
    assert ri.ok
    # sampling far too shallow to ever see the Deep record
    ex = TpuExplorer(model, store_trace=False, host_seen=True,
                     sample_cfg=(3, 2, 3))
    r = ex.run()
    assert r.ok
    assert (r.generated, r.distinct) == (ri.generated, ri.distinct)
