r"""Behavior-graph liveness checking (engine/liveness.py).

Targets the corpus's temporal-property obligations (VERDICT round-1
Missing #1): the Liveness-chapter properties, MCAlternatingBit's leads-to,
RealTime's expected-to-fail property, and MCInnerSerial's AlwaysResponds —
each with a fairness-free negative control proving the checks are not
vacuous.
"""

import os

from jaxmc.front.cfg import parse_cfg
from jaxmc.sem.modules import Loader, bind_model
from jaxmc.engine.explore import Explorer

from conftest import REFERENCE, needs_reference

# every test here loads reference-corpus specs (driver env only)
pytestmark = [needs_reference]

SS = os.path.join(REFERENCE, "examples/SpecifyingSystems")


def run(spec_path, cfg_text=None, cfg_path=None):
    cfg = parse_cfg(cfg_text if cfg_text is not None
                    else open(cfg_path).read())
    m = Loader([os.path.dirname(spec_path)]).load_path(spec_path)
    return Explorer(bind_model(m, cfg)).run()


class TestLiveHourClock:
    SPEC = os.path.join(SS, "Liveness/LiveHourClock.tla")

    def test_all_properties_hold_under_fairness(self):
        # PROPERTIES AlwaysTick AllTimes TypeInvariance
        # (LiveHourClock.cfg) — []<><<A>>_v, \A-quantified []<>, and []P
        r = run(self.SPEC, cfg_path=os.path.join(
            SS, "Liveness/LiveHourClock.cfg"))
        assert r.ok
        assert not any("NOT checked" in w for w in r.warnings)

    def test_alwaystick_violated_without_fairness(self):
        # HC alone permits infinite stuttering: []<><<HCnxt>>_hr fails
        r = run(self.SPEC, "SPECIFICATION HC\nPROPERTIES AlwaysTick\n")
        assert not r.ok
        assert r.violation.kind == "property"
        assert "AlwaysTick" in r.violation.name

    def test_alltimes_violated_without_fairness(self):
        r = run(self.SPEC, "SPECIFICATION HC\nPROPERTIES AllTimes\n")
        assert not r.ok
        assert "AllTimes" in r.violation.name


class TestAlternatingBit:
    SPEC = os.path.join(SS, "TLC/MCAlternatingBit.tla")
    NOFAIR = """INIT ABInit
NEXT ABNext
CONSTANTS
  Data = {d1, d2}
  msgQLen = 2
  ackQLen = 2
CONSTRAINT SeqConstraint
PROPERTIES SentLeadsToRcvd
CHECK_DEADLOCK FALSE
"""

    def test_sent_leadsto_rcvd_holds_under_wf_sf(self):
        # ABSpec's fairness is WF(ReSndMsg) /\ WF(SndAck) /\ SF(RcvMsg)
        # /\ SF(RcvAck) (AlternatingBit.tla:72-75) — the ~> property needs
        # all of it
        r = run(self.SPEC, cfg_path=os.path.join(
            SS, "TLC/MCAlternatingBit.cfg"))
        assert r.ok
        assert not any("SentLeadsToRcvd" in w for w in r.warnings)

    def test_violated_without_fairness(self):
        r = run(self.SPEC, self.NOFAIR)
        assert not r.ok
        assert "SentLeadsToRcvd" in r.violation.name


class TestRealTimeHourClock:
    def test_error_temporal_found_violated(self):
        # the cfg's PROPERTY ErrorTemporal ([]((now # 4) => <>[](now # 4)),
        # MCRealTimeHourClock.tla:43) is expected to FAIL — finding the
        # violation is the pass criterion
        r = run(os.path.join(SS, "RealTime/MCRealTimeHourClock.tla"),
                cfg_path=os.path.join(SS,
                                      "RealTime/MCRealTimeHourClock.cfg"))
        assert not r.ok
        assert r.violation.kind == "property"
        assert "ErrorTemporal" in r.violation.name
        assert r.distinct == 216 and r.generated == 696


class TestInnerSerial:
    SPEC = os.path.join(SS, "AdvancedExamples/MCInnerSerial.tla")
    NOFAIR = """INIT Init
NEXT Next
CONSTANTS
  Reg = {r1}
  Adr = {a1}
  Val = {v1, v2}
  Proc = {p1, p2}
  InitMem <- MCInitMem
  InitWr = InitWr
  Done = Done
  MaxQLen = 1
  Nat <- MCNat
CONSTRAINT Constraint
PROPERTY AlwaysResponds
CHECK_DEADLOCK FALSE
"""

    def test_always_responds_violated_without_fairness(self):
        # the quantified ~> property needs InnerSerial's WF conjuncts
        # (InnerSerial.tla:109-119); without them a pending request can
        # stutter forever. (The fairness-ful positive run is the golden
        # testout2 model — covered by test_innerserial_matches_golden_
        # testout2, which now also checks AlwaysResponds.)
        r = run(self.SPEC, self.NOFAIR)
        assert not r.ok
        assert "AlwaysResponds" in r.violation.name


class TestFairnessAsProperty:
    """PROPERTY formulas that are themselves fairness/liveness formulas
    (VERDICT r2 #3): MCLiveInternalMemory.cfg:4-7 checks `Liveness`
    (\\A p : WF_vars(Do(p)) /\\ WF_vars(Rsp(p))) as a property, and
    MCLiveWriteThroughCache.cfg:4-10 checks LM_Inner_LISpec (a full fair
    spec whose Init/[][Next]_v half the refinement checker covers) and
    LM_Inner_Liveness (the hand-instantiated []<>~Enabled \\/ []<><<A>>_v
    construction, MCLiveWriteThroughCache.tla:129-143). All must check
    with ZERO 'NOT checked' warnings, and be found violated when the
    specification's own fairness is dropped."""

    LIM = os.path.join(SS, "Liveness/MCLiveInternalMemory.tla")
    WTC = os.path.join(SS, "Liveness/MCLiveWriteThroughCache.tla")
    LIM_CONSTS = """CONSTANTS
  Send  <- MCSend
  Reply <- MCReply
  InitMemInt <- MCInitMemInt
  Proc = {p1, p2}
  Adr = {a1}
  Val = {v1, v2}
  NoVal = NoVal
"""
    WTC_CONSTS = LIM_CONSTS + "  QLen = 1\n"

    def test_mclive_internal_memory_zero_warnings(self):
        # PROPERTY LivenessProperty (~>) + PROPERTY Liveness (WF atoms):
        # both fully checked under LISpec's fairness
        r = run(self.LIM, cfg_path=os.path.join(
            SS, "Liveness/MCLiveInternalMemory.cfg"))
        assert r.ok
        assert (r.distinct, r.generated) == (4408, 21400)
        assert not any("NOT checked" in w for w in r.warnings), r.warnings

    def test_mclive_wtc_zero_warnings(self):
        # PROPERTY LM_Inner_LISpec (refinement half stepwise + fairness
        # half over the behavior graph) + PROPERTY LM_Inner_Liveness
        r = run(self.WTC, cfg_path=os.path.join(
            SS, "Liveness/MCLiveWriteThroughCache.cfg"))
        assert r.ok
        assert (r.distinct, r.generated) == (5196, 28170)
        assert not any("NOT checked" in w for w in r.warnings), r.warnings

    def test_liveness_property_violated_without_fairness(self):
        # negative control: under ISpec (no fairness) a busy processor
        # may stutter forever — WF_vars(Do(p)) fails as a property
        r = run(self.LIM, "SPECIFICATION ISpec\nPROPERTY Liveness\n"
                + self.LIM_CONSTS + "CHECK_DEADLOCK FALSE\n")
        assert not r.ok
        assert r.violation.kind == "property"
        assert "Liveness" in r.violation.name

    def test_lm_inner_liveness_violated_without_fairness(self):
        r = run(self.WTC, "SPECIFICATION Spec\nPROPERTY LM_Inner_Liveness\n"
                + self.WTC_CONSTS + "CHECK_DEADLOCK FALSE\n")
        assert not r.ok
        assert "LM_Inner_Liveness" in r.violation.name

    def test_lm_inner_lispec_fairness_half_violated_without_fairness(self):
        # the spec-shaped property: its refinement half still holds under
        # the unfair spec, so the violation MUST come from the fairness
        # half (the Liveness2 disjunction)
        r = run(self.WTC, "SPECIFICATION Spec\nPROPERTY LM_Inner_LISpec\n"
                + self.WTC_CONSTS + "CHECK_DEADLOCK FALSE\n")
        assert not r.ok
        assert "LM_Inner_LISpec" in r.violation.name
        assert not any("NOT checked" in w for w in r.warnings), r.warnings


class TestDeviceLiveness:
    """The jax backend streams the behavior graph (kept states, edges,
    parents, labels) to the host and runs the SAME LivenessChecker the
    interp uses — verdict parity on every corpus liveness model the
    kernel compiler accepts (backend/bfs.py _LiveGraph/_check_live)."""

    def run_jax(self, spec_path, cfg_text=None, cfg_path=None, **kw):
        from jaxmc.backend.bfs import TpuExplorer
        cfg = parse_cfg(cfg_text if cfg_text is not None
                        else open(cfg_path).read())
        m = Loader([os.path.dirname(spec_path)]).load_path(spec_path)
        return TpuExplorer(bind_model(m, cfg), **kw).run()

    def test_livehourclock_properties_hold(self):
        r = self.run_jax(TestLiveHourClock.SPEC, cfg_path=os.path.join(
            SS, "Liveness/LiveHourClock.cfg"))
        assert r.ok
        assert not any("NOT checked" in w for w in r.warnings)

    def test_alwaystick_violated_without_fairness(self):
        r = self.run_jax(TestLiveHourClock.SPEC,
                         "SPECIFICATION HC\nPROPERTIES AlwaysTick\n")
        assert not r.ok
        assert r.violation.kind == "property"
        assert "AlwaysTick" in r.violation.name

    def test_sent_leadsto_rcvd_device_negative(self):
        # fairness-free: the device-built behavior graph must expose the
        # stuttering lasso inside ~Rcvd (proves edges/graph are real)
        r = self.run_jax(os.path.join(SS, "TLC/MCAlternatingBit.tla"),
                         TestAlternatingBit.NOFAIR)
        assert not r.ok
        assert "SentLeadsToRcvd" in r.violation.name

    def test_sent_leadsto_rcvd_device_host_seen(self):
        # same verdicts through the chunked native-store path (its edge
        # accumulation is per-chunk with level-deferred resolution)
        from jaxmc import native_store
        import pytest
        if not native_store.is_available():
            pytest.skip("no native toolchain")
        spec = os.path.join(SS, "TLC/MCAlternatingBit.tla")
        r = self.run_jax(spec, cfg_path=os.path.join(
            SS, "TLC/MCAlternatingBit.cfg"), host_seen=True, chunk=64)
        assert r.ok and r.distinct == 240
        r2 = self.run_jax(spec, TestAlternatingBit.NOFAIR,
                          host_seen=True, chunk=64)
        assert not r2.ok
        assert "SentLeadsToRcvd" in r2.violation.name

    def test_always_only_property_no_edge_log(self):
        # '[]P'-only properties need states but no edge log
        # (collect_edges=False): the device-seen step emits no cand
        # tensor on this path — regression for a KeyError
        r = self.run_jax(TestLiveHourClock.SPEC,
                         "SPECIFICATION HC\nPROPERTIES TypeInvariance\n")
        assert r.ok and r.distinct == 12

    def test_truncated_run_warns(self):
        r = self.run_jax(TestLiveHourClock.SPEC, cfg_path=os.path.join(
            SS, "Liveness/LiveHourClock.cfg"), max_states=3)
        assert r.truncated
        assert any("truncated" in w for w in r.warnings)


class TestCheckpointedLiveness:
    def test_resume_preserves_edge_log(self, tmp_path):
        # liveness after --resume must see pre-checkpoint edges: the
        # fairness-free SentLeadsToRcvd violation must still be found
        # when the search ran in two halves
        spec = os.path.join(SS, "TLC/MCAlternatingBit.tla")
        cfg_text = TestAlternatingBit.NOFAIR
        ckpt = str(tmp_path / "ab.ckpt")
        m1 = Loader([os.path.dirname(spec)]).load_path(spec)
        r1 = Explorer(bind_model(m1, parse_cfg(cfg_text)), max_states=50,
                      checkpoint_path=ckpt, checkpoint_every=0.0).run()
        assert r1.truncated
        m2 = Loader([os.path.dirname(spec)]).load_path(spec)
        r2 = Explorer(bind_model(m2, parse_cfg(cfg_text)),
                      resume_from=ckpt).run()
        assert not r2.ok
        assert "SentLeadsToRcvd" in r2.violation.name
