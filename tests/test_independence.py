r"""Independence-driven hot path (ISSUE 15): per-element container
bounds, commuting-arm regrouping, opt-in POR, and bounds-sized engines.

Pins, all on repo-local fixtures:
  * element-atom footprints: portoy's Step arms commute pairwise
    (cnt[p1]/cnt[p2]/cnt[p3] are distinct atoms), symtoy's shared
    owner/used keep its arms dependent; the group planner beats
    contiguous packing only when it genuinely saves dispatches.
  * per-element bounds: symtoy's EXCEPT-guard container proves
    turns in [0,2]^P — proven element lanes, zero guarded lanes,
    bits/state halved, counts/traces bit-identical analyze on/off;
    record fields keep PER-KEY intervals.
  * verdict classification: dyntoy's multi-binder and nested dynamic \E
    arms are predicted with ground.py's exact reason strings (zero
    futile builds), quantifiers over Nat / unbounded quantifiers
    predict kernel2's exact wording, and the corpus pin_derived
    mechanism fails LOUDLY when the predictor loses coverage.
  * regrouping: byte-identical counts/traces with regrouping on/off
    AND under a deliberately permuted plan (the provenance-restore
    property), on the grouped host_seen path and the mesh-D2 grouped
    expand.
  * POR: --por preserves the ok/deadlock/invariant verdicts across
    serial/parallel/level/resident session configs, reports traces
    that REPLAY under unreduced semantics, cuts portoy's explored
    states >= 30%, and survives a SIGKILL mid-run + resume (chaos).
  * bounds-sized engines: a COLD resident run of the fully-proven
    fixture takes the `predicted` capacity rung and pays exactly one
    compile — no growth-retry recompiles.
"""

import os
import subprocess
import sys

import pytest

from jaxmc import obs
from jaxmc.front.cfg import ModelConfig, parse_cfg
from jaxmc.sem.modules import Loader, bind_model
from jaxmc.engine.explore import Explorer, format_trace

SPECS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "specs")
REPO = os.path.dirname(SPECS)


def load(name, cfg_name=None, no_deadlock=False):
    m = Loader([SPECS]).load_path(os.path.join(SPECS, name + ".tla"))
    if cfg_name is None:
        cfg_name = name
    p = os.path.join(SPECS, cfg_name + ".cfg")
    cfg = parse_cfg(open(p).read()) if os.path.exists(p) \
        else ModelConfig(specification="Spec")
    if no_deadlock:
        cfg.check_deadlock = False
    return bind_model(m, cfg)


def write_spec(tmp_path, name, body):
    sp = tmp_path / f"{name}.tla"
    sp.write_text(body)
    return str(sp)


@pytest.fixture(autouse=True)
def _isolated_profiles(tmp_path, monkeypatch):
    monkeypatch.setenv("JAXMC_PROFILE_STORE", str(tmp_path / "prof"))


# ------------------------------------------------- footprints + planner

class TestFootprints:
    def test_portoy_element_atoms_commute(self):
        from jaxmc.analyze.independence import independence_report
        rep = independence_report(load("portoy", "portoy_ok",
                                       no_deadlock=True))
        by = {}
        for i, lb in enumerate(rep.labels):
            by[lb] = i
        s1, s2, s3, fire = (by["Step(p1)"], by["Step(p2)"],
                            by["Step(p3)"], by["Fire"])
        assert rep.commutes[s1][s2] and rep.commutes[s2][s3]
        # Fire reads cnt[p1] (through the CONSTANT P1): dependent on
        # Step(p1) only
        assert not rep.commutes[s1][fire]
        assert rep.commutes[s2][fire] and rep.commutes[s3][fire]
        # no invariant in this cfg: the globally-commuting Steps are
        # por-safe, Step(p1) (dependent on Fire) is not
        assert sorted(rep.por_safe) == sorted((s2, s3))
        fp = rep.footprints[s1]
        assert ("cnt", None) not in fp.writes  # element, not whole-var

    def test_symtoy_shared_vars_block_commutation(self):
        from jaxmc.analyze.independence import independence_report
        rep = independence_report(load("symtoy", no_deadlock=True))
        assert rep.commuting_pairs() == 0  # owner/used shared by Grabs
        # ...but the turns access is still per-element
        grabs = [fp for fp in rep.footprints if fp.label == "Next"]
        assert any(("turns", k) in fp.writes and k is not None
                   for fp in grabs for _v, k in fp.writes)

    def test_plan_arm_groups_shrinks_or_keeps_contiguous(self):
        from jaxmc.analyze.independence import plan_arm_groups
        n = 7
        weights = [2, 2, 2, 3, 1, 1, 1]
        all_commute = [[i != j for j in range(n)] for i in range(n)]
        arm_of = list(range(n))
        groups = plan_arm_groups(weights, arm_of, all_commute, 4)
        assert len(groups) == 3  # contiguous needs 4
        assert sorted(i for g in groups for i in g) == list(range(n))
        for g in groups:
            assert sum(weights[i] for i in g) <= 4
        # no matrix -> legacy contiguous
        base = plan_arm_groups(weights, arm_of, None, 4)
        assert base == [[0, 1], [2], [3, 4], [5, 6]]
        # nothing commutes -> cliques are singletons; contiguous wins
        none_commute = [[False] * n for _ in range(n)]
        assert plan_arm_groups(weights, arm_of, none_commute, 4) == base

    def test_plan_respects_env_optout(self, monkeypatch):
        from jaxmc.analyze.independence import plan_arm_groups
        monkeypatch.setenv("JAXMC_ANALYZE_INDEP", "0")
        weights = [2, 2, 2, 3, 1, 1, 1]
        mat = [[i != j for j in range(7)] for i in range(7)]
        assert plan_arm_groups(weights, list(range(7)), mat, 4) == \
            [[0, 1], [2], [3, 4], [5, 6]]


# ------------------------------------------------- dynamic element keys

class TestDynamicKeys:
    """ISSUE 18: symbolic key-disjointness — tuple keys, binder-domain
    key sets, static key arithmetic, and named bail reasons."""

    def test_msgstoy_send_arms_element_commuting(self):
        from jaxmc.analyze.independence import independence_report
        rep = independence_report(load("msgstoy", no_deadlock=True))
        by = {lb: i for i, lb in enumerate(rep.labels)}
        sends = [by[f"Send({p})"] for p in ("p1", "p2", "p3")]
        for i in sends:
            fp = rep.footprints[i]
            assert fp.exact
            assert ("msgs", None) not in fp.writes
            assert fp.key_class() == "element-commuting"
        for i in sends:
            for j in sends:
                if i != j:
                    assert rep.commutes[i][j]
        # Flush reads msgs[P1] through the CONSTANT: only Send(p1)
        # clashes with it, the other Sends (and Tick) stay por-safe
        assert not rep.commutes[by["Send(p1)"]][by["Flush"]]
        assert rep.commutes[by["Send(p2)"]][by["Flush"]]
        assert sorted(rep.por_safe) == sorted(
            (by["Send(p2)"], by["Send(p3)"], by["Tick"]))

    def test_msgstoy_dynamic_exists_binds_domain_keyset(self):
        from jaxmc.analyze.independence import (_KeySet,
                                                independence_report)
        rep = independence_report(load("msgstoy", no_deadlock=True))
        tick = rep.footprints[rep.labels.index("Tick")]
        ks = [k for v, k in tick.writes if v == "clock"]
        assert len(ks) == 1 and isinstance(ks[0], _KeySet)
        assert ks[0].vals == frozenset((1, 2))  # 1..T through the cfg
        assert tick.key_class() == "element-commuting"

    def test_key_interference_rules(self):
        from jaxmc.analyze.independence import (_interfere, _KeySet,
                                                _TupleKey)
        f = frozenset
        ks12, ks23, ks45 = (_KeySet((1, 2)), _KeySet((2, 3)),
                            _KeySet((4, 5)))
        assert _interfere(f({("v", ks12)}), f({("v", ks23)}))
        assert not _interfere(f({("v", ks12)}), f({("v", ks45)}))
        assert _interfere(f({("v", ks12)}), f({("v", 2)}))
        assert not _interfere(f({("v", ks12)}), f({("v", 3)}))
        assert _interfere(f({("v", None)}), f({("v", ks12)}))
        assert not _interfere(f({("v", ks12)}), f({("w", ks12)}))
        # tuple keys compare componentwise and never equal a scalar
        t12 = _TupleKey((1, 2))
        assert _interfere(f({("v", t12)}), f({("v", _TupleKey((1, 2)))}))
        assert not _interfere(f({("v", t12)}),
                              f({("v", _TupleKey((1, 3)))}))
        assert not _interfere(f({("v", t12)}), f({("v", 1)}))
        assert _interfere(
            f({("v", t12)}),
            f({("v", _TupleKey((_KeySet((1, 9)), 2)))}))
        assert not _interfere(
            f({("v", t12)}),
            f({("v", _TupleKey((_KeySet((3, 9)), 2)))}))

    def test_static_key_arithmetic(self):
        from jaxmc.analyze.independence import (_key_arith, _KeySet,
                                                _NOKEY)
        assert _key_arith("+", 2, 3) == 5
        assert _key_arith("-", 7, 2) == 5
        assert _key_arith("-", _KeySet((1, 2)), 1) == _KeySet((0, 1))
        assert _key_arith("+", "a", 1) is _NOKEY
        assert _key_arith("+", True, 1) is _NOKEY

    def test_tuple_keys_resolve_through_split_bindings(self, tmp_path):
        # the raft message-table shape at analysis level: arms writing
        # distinct <<p, q>> channels commute element-wise, and static
        # +1 arithmetic resolves split-binder keys to concrete ints
        spec = write_spec(tmp_path, "tuptoy", r"""
---------------------------- MODULE tuptoy ----------------------------
EXTENDS Naturals
CONSTANTS Procs
VARIABLES msgs, acks

Chans == {<<p, q>> : p \in Procs, q \in Procs}

Init == /\ msgs = [c \in Chans |-> 0]
        /\ acks = [n \in 1..3 |-> 0]

Send(p, q) == /\ msgs[<<p, q>>] < 2
              /\ msgs' = [msgs EXCEPT ![<<p, q>>] = @ + 1]
              /\ UNCHANGED acks

Shift(n) == /\ acks[n + 1] < 2
            /\ acks' = [acks EXCEPT ![n + 1] = @ + 1]
            /\ UNCHANGED msgs

Next == (\E p \in Procs, q \in Procs : Send(p, q))
          \/ (\E n \in 1..2 : Shift(n))
=======================================================================
""")
        from jaxmc.analyze.independence import (_TupleKey,
                                                independence_report)
        cfg = parse_cfg("INIT Init\nNEXT Next\n"
                        "CONSTANTS\n  Procs = {a, b}\n")
        cfg.check_deadlock = False
        m = bind_model(Loader([str(tmp_path)]).load_path(spec), cfg)
        rep = independence_report(m)
        by = {lb: i for i, lb in enumerate(rep.labels)}
        sab, sba = by["Send(a, b)"], by["Send(b, a)"]
        assert rep.commutes[sab][sba]
        fp = rep.footprints[sab]
        assert fp.exact and ("msgs", None) not in fp.writes
        assert any(isinstance(k, _TupleKey) for _v, k in fp.writes)
        # Shift(1) writes acks[2], Shift(2) writes acks[3]: disjoint
        assert rep.commutes[by["Shift(1)"]][by["Shift(2)"]]
        assert ("acks", 2) in rep.footprints[by["Shift(1)"]].writes
        assert ("acks", 3) in rep.footprints[by["Shift(2)"]].writes
        # every arm resolved to element atoms
        assert all(fp.key_class() == "element-commuting"
                   for fp in rep.footprints)

    def test_bail_reason_named(self, tmp_path):
        spec = write_spec(tmp_path, "bailtoy", r"""
---------------------------- MODULE bailtoy ---------------------------
EXTENDS Naturals
VARIABLES x

Init == x = 0

Rec(n) == IF n = 0 THEN x' = x + 1 ELSE Rec(n - 1)

Next == Rec(x)
=======================================================================
""")
        from jaxmc.analyze.independence import independence_report
        cfg = parse_cfg("INIT Init\nNEXT Next\n")
        cfg.check_deadlock = False
        m = bind_model(Loader([str(tmp_path)]).load_path(spec), cfg)
        rep = independence_report(m)
        fp = rep.footprints[0]
        assert not fp.exact
        assert fp.bail_reason and "Rec" in fp.bail_reason
        assert "full-footprint bail" in fp.key_class()
        assert "Rec" in fp.key_class()


# ------------------------------------------------- per-element bounds

class TestPerElementBounds:
    def test_symtoy_except_guard_container_proves(self):
        from jaxmc.analyze.bounds import infer_state_bounds
        rep = infer_state_bounds(load("symtoy", no_deadlock=True))
        assert rep is not None and rep.converged
        assert rep.lane_bounds().get("turns") == (0, 2)
        eb = rep.element_bounds()["turns"]
        assert eb.rng is not None and eb.rng.all == (0, 2)

    def test_record_fields_keep_per_key_intervals(self, tmp_path):
        spec = write_spec(tmp_path, "rectoy", r"""
---------------------------- MODULE rectoy ----------------------------
EXTENDS Naturals
VARIABLES r

Init == r = [small |-> 0, big |-> 100]

Bump == /\ r.small < 3
        /\ r' = [r EXCEPT !.small = @ + 1]

Next == Bump

Spec == Init /\ [][Next]_<<r>>
=======================================================================
""")
        from jaxmc.analyze.bounds import infer_state_bounds
        m = bind_model(Loader([str(tmp_path)]).load_path(spec),
                       ModelConfig(specification="Spec",
                                   check_deadlock=False))
        rep = infer_state_bounds(m)
        assert rep is not None and rep.converged
        eb = rep.element_bounds()["r"]
        assert eb.keys["small"].all == (0, 3)   # strong field update
        assert eb.keys["big"].all == (100, 100)
        assert rep.lane_bounds()["r"] == (0, 100)

    def test_symtoy_proven_element_lanes_device_parity(self):
        pytest.importorskip("jax")
        from jaxmc.backend.bfs import TpuExplorer
        ri = Explorer(load("symtoy", no_deadlock=True)).run()
        runs = {}
        for tag, env in (("on", {}), ("off",
                                      {"JAXMC_ANALYZE_BOUNDS": "0"})):
            old = {k: os.environ.get(k) for k in env}
            os.environ.update(env)
            tel = obs.Telemetry()
            try:
                with obs.use(tel):
                    r = TpuExplorer(load("symtoy", no_deadlock=True),
                                    store_trace=False).run()
            finally:
                for k, v in old.items():
                    (os.environ.pop(k, None) if v is None
                     else os.environ.__setitem__(k, v))
            runs[tag] = (r, tel)
        for r, _t in runs.values():
            assert (r.distinct, r.generated) == (ri.distinct,
                                                 ri.generated)
        tel_on, tel_off = runs["on"][1], runs["off"][1]
        # the 3 turns element lanes prove; nothing stays guarded
        assert tel_on.gauges.get("analyze.proven_lanes") == 3
        assert tel_on.gauges.get("layout.pack_guarded_lanes") == 0
        assert tel_off.gauges.get("analyze.proven_lanes") == 0
        assert tel_on.gauges.get("layout.bits_per_state") < \
            tel_off.gauges.get("layout.bits_per_state")

    def test_state_space_estimates(self):
        from jaxmc.analyze.bounds import (infer_state_bounds,
                                          state_space_estimate)
        m = load("portoy", "portoy_ok", no_deadlock=True)
        assert state_space_estimate(m, infer_state_bounds(m)) == 432
        m = load("symtoy", no_deadlock=True)
        est = state_space_estimate(m, infer_state_bounds(m))
        assert est is not None and est >= 22  # covers the real 22
        # racing unbounded counters must NOT produce an estimate
        m = load("transfer_scaled")
        assert state_space_estimate(m, infer_state_bounds(m)) is None


# ------------------------------------------------- verdict classification

class TestVerdictClasses:
    def test_dyntoy_predicted_equals_built(self):
        pytest.importorskip("jax")
        from jaxmc import native_store
        from jaxmc.backend.bfs import TpuExplorer
        from jaxmc.analyze import predict_arm_demotions
        from jaxmc.compile.ground import (DYN_NESTED_MSG,
                                          DYN_SHAPE_MSG, split_arms)
        m = load("dyntoy")
        arms = split_arms(m)
        pred = {arms[i].label: r for i, r in
                predict_arm_demotions(m, arms).items()}
        assert pred == {"Pair": DYN_SHAPE_MSG, "Relay": DYN_NESTED_MSG}
        if not native_store.is_available():
            pytest.skip("hybrid needs the native store")
        old = os.environ.get("JAXMC_ANALYZE_PREDICT")
        os.environ["JAXMC_ANALYZE_PREDICT"] = "0"
        try:
            ex = TpuExplorer(load("dyntoy"), store_trace=False,
                             host_seen=True)
        finally:
            (os.environ.pop("JAXMC_ANALYZE_PREDICT", None) if old is
             None else os.environ.__setitem__("JAXMC_ANALYZE_PREDICT",
                                              old))
        built = {a.label: w for a, w in ex.fb_arms}
        assert built == pred  # identical wording, both classes

    def test_quantifier_domain_classes_predicted(self, tmp_path):
        """The two new classification classes carry kernel2's raise-site
        constants (UNBOUNDED_QUANTIFIER_MSG / cannot_enumerate_message
        — the same one-constant contract the unroll message pins).  No
        engine build here: a spec quantifying over Nat in an enabled
        guard is uncheckable by EVERY backend, so the predictor is the
        only component that can name it before the crash."""
        spec = write_spec(tmp_path, "quanttoy", r"""
--------------------------- MODULE quanttoy ---------------------------
EXTENDS Naturals
VARIABLES n

Init == n = 0

OverNat == /\ \A m \in Nat : m >= 0
           /\ n' = n + 1

Unbounded == /\ \A m : m = m
             /\ n' = n

Next == OverNat \/ Unbounded

Spec == Init /\ [][Next]_<<n>>
=======================================================================
""")
        from jaxmc.analyze import predict_arm_demotions
        from jaxmc.compile.ground import split_arms
        from jaxmc.compile.kernel2 import (UNBOUNDED_QUANTIFIER_MSG,
                                           cannot_enumerate_message)
        from jaxmc.sem.values import InfiniteSet
        m = bind_model(Loader([str(tmp_path)]).load_path(spec),
                       ModelConfig(specification="Spec",
                                   check_deadlock=False))
        arms = split_arms(m)
        pred = {arms[i].label: r for i, r in
                predict_arm_demotions(m, arms).items()}
        assert pred.get("OverNat") == \
            cannot_enumerate_message(InfiniteSet("Nat")) == \
            "cannot enumerate Nat"
        assert pred.get("Unbounded") == UNBOUNDED_QUANTIFIER_MSG == \
            "unbounded quantifier"

    def test_predictor_still_silent_on_compilable_fixtures(self):
        from jaxmc.analyze import predict_arm_demotions
        from jaxmc.compile.ground import split_arms
        for name, cfg in (("portoy", "portoy_ok"),
                          ("viewtoy", None), ("constoy", None)):
            m = load(name, cfg, no_deadlock=True)
            assert predict_arm_demotions(m, split_arms(m)) == {}, name

    def test_corpus_pin_derived_mechanism(self, monkeypatch):
        pytest.importorskip("jax")
        from jaxmc import native_store
        if not native_store.is_available():
            pytest.skip("hybrid needs the native store")
        from jaxmc.corpus import CASES, run_case
        case = next(c for c in CASES
                    if (c.cfg_path() or "").endswith("dyntoy.cfg"))
        assert case.pin_derived
        s, d, _r, mode = run_case(case, "jax")
        assert s == "pass" and mode == "interp-arms"
        assert "[pin derived by predictor]" in d
        # a predictor that loses coverage FAILS the case loudly
        import jaxmc.analyze as _an
        monkeypatch.setattr(_an, "predict_arm_demotions",
                            lambda model, arms: {})
        s2, d2, _r2, _m2 = run_case(case, "jax")
        assert s2 == "fail" and "PREDICTOR REGRESSION" in d2
        # ...and JAXMC_PIN_DERIVE=0 restores the measured pin
        monkeypatch.setenv("JAXMC_PIN_DERIVE", "0")
        s3, d3, _r3, m3 = run_case(case, "jax")
        assert s3 == "pass" and m3 == "interp-arms"
        assert "[pin derived by predictor]" not in d3


# ------------------------------------------------- regroup parity

def _device_run(model, tel=None, **kw):
    from jaxmc.backend.bfs import TpuExplorer
    tel = tel or obs.Telemetry()
    with obs.use(tel):
        ex = TpuExplorer(model, **kw)
        r = ex.run()
    return r, tel


@pytest.mark.usefixtures("_isolated_profiles")
class TestRegroupParity:
    @pytest.mark.parametrize("name,cfg,ndl", [
        ("portoy", "portoy_bad", False),
        ("symtoy", "symtoy", True),
    ])
    def test_grouped_host_seen_byte_identical(self, name, cfg, ndl,
                                              monkeypatch):
        pytest.importorskip("jax")
        from jaxmc import native_store
        if not native_store.is_available():
            pytest.skip("needs the native store")
        monkeypatch.setenv("JAXMC_FUSED_MAX_INSTANCES", "2")
        results = {}
        for indep in ("1", "0"):
            monkeypatch.setenv("JAXMC_ANALYZE_INDEP", indep)
            r, tel = _device_run(load(name, cfg, no_deadlock=ndl),
                                 host_seen=True)
            assert tel.gauges.get("expand.fused_groups", 0) >= 2
            results[indep] = r
        a, b = results["1"], results["0"]
        assert (a.distinct, a.generated, a.ok) == \
            (b.distinct, b.generated, b.ok)
        if a.violation is not None:
            assert format_trace(a.violation) == \
                format_trace(b.violation)

    def test_permuted_plan_provenance_restored(self, monkeypatch):
        """ANY group permutation must be byte-identical — the scatter
        at the merge restores original instance order."""
        pytest.importorskip("jax")
        from jaxmc import native_store
        if not native_store.is_available():
            pytest.skip("needs the native store")
        from jaxmc.backend.bfs import TpuExplorer
        monkeypatch.setenv("JAXMC_FUSED_MAX_INSTANCES", "2")
        base, _ = _device_run(load("portoy", "portoy_bad"),
                              host_seen=True)
        monkeypatch.setattr(
            TpuExplorer, "_arm_group_plan",
            lambda self, fused_max: [[3, 1], [2, 0]])
        perm, tel = _device_run(load("portoy", "portoy_bad"),
                                host_seen=True)
        assert (perm.distinct, perm.generated, perm.ok) == \
            (base.distinct, base.generated, base.ok)
        assert format_trace(perm.violation) == \
            format_trace(base.violation)

    def test_mesh_d2_grouped_byte_identical(self, monkeypatch):
        pytest.importorskip("jax")
        from jaxmc.backend.mesh import MeshExplorer
        monkeypatch.setenv("JAXMC_FUSED_MAX_INSTANCES", "2")
        monkeypatch.setenv("JAXMC_MESH_GROUPED", "1")
        results = {}
        for indep in ("1", "0"):
            monkeypatch.setenv("JAXMC_ANALYZE_INDEP", indep)
            tel = obs.Telemetry()
            with obs.use(tel):
                r = MeshExplorer(load("portoy", "portoy_ok",
                                      no_deadlock=True)).run()
            assert tel.gauges.get("mesh.grouped_expand", 0) >= 2
            results[indep] = r
        a, b = results["1"], results["0"]
        assert (a.distinct, a.generated) == (b.distinct, b.generated) \
            == (150, 366)


# ------------------------------------------------- POR

def _replays(model, trace):
    """Every step of a reported trace must be a REAL transition of the
    unreduced semantics (the --por trace-validity contract)."""
    from jaxmc.sem.enumerate import enumerate_init, enumerate_next
    ctx = model.ctx()
    inits = enumerate_init(model.init, ctx, model.vars)
    assert trace[0][0] in inits, "trace root is not an initial state"
    for (s0, _l0), (s1, _l1) in zip(trace, trace[1:]):
        succs = [succ for succ, _ in
                 enumerate_next(model.next, ctx, model.vars, s0)]
        assert s1 in succs, "trace step is not an unreduced transition"


class TestPOR:
    def test_por_reduction_and_trace_replay(self):
        m = load("portoy", "portoy_bad")
        tel = obs.Telemetry()
        with obs.use(tel):
            r = Explorer(m, por=True).run()
        full = Explorer(load("portoy", "portoy_bad")).run()
        assert not r.ok and r.violation.kind == "invariant" \
            and full.violation.kind == "invariant"
        assert r.distinct < full.distinct
        assert tel.gauges.get("por.enabled") is True
        assert tel.gauges.get("por.ample_ratio") > 0
        assert tel.gauges.get("por.reduced_states") == r.distinct
        _replays(m, r.violation.trace)

    def test_por_thirty_percent_reduction_acceptance(self):
        full = Explorer(load("portoy", "portoy_ok",
                             no_deadlock=True)).run()
        red = Explorer(load("portoy", "portoy_ok", no_deadlock=True),
                       por=True).run()
        assert full.ok and red.ok
        assert red.distinct <= 0.7 * full.distinct, \
            f"{red.distinct} vs {full.distinct}: < 30% reduction"

    @pytest.mark.parametrize("name,cfg", [("portoy", "portoy_ok"),
                                          ("msgstoy", "msgstoy")])
    def test_device_por_thirty_percent_reduction(self, name, cfg):
        """The ample mask INSIDE the fused device step, on the static
        (portoy) and the dynamic-key (msgstoy) fixture: the unreduced
        device run meets the manifest pins, the reduced one keeps the
        verdict with >= 30% fewer distinct states and no interpreter
        demotion (leg 5 of the deleted `make por-check`, ISSUE 43)."""
        pytest.importorskip("jax")
        from jaxmc import native_store
        if not native_store.is_available():
            pytest.skip("host_seen needs the native store")
        from jaxmc.corpus import case_for_cfg
        pin = case_for_cfg(cfg + ".cfg")
        full, _ = _device_run(load(name, cfg, no_deadlock=True),
                              host_seen=True)
        assert full.ok and (full.generated, full.distinct) == \
            (pin.generated, pin.distinct)
        red, tel = _device_run(load(name, cfg, no_deadlock=True),
                               host_seen=True, por=True)
        assert red.ok
        assert tel.gauges.get("por.engine") == "device"
        assert tel.gauges.get("por.enabled") is True
        assert tel.gauges.get("por.device_masked_arms", 0) > 0
        assert red.distinct <= 0.7 * pin.distinct, \
            f"{red.distinct} vs {pin.distinct}: < 30% reduction"

    def test_por_deadlock_verdict_and_replay(self):
        m = load("portoy", "portoy")
        r = Explorer(m, por=True).run()
        assert not r.ok and r.violation.kind == "deadlock"
        _replays(m, r.violation.trace)
        # the deadlock state must genuinely deadlock unreduced
        from jaxmc.sem.enumerate import enumerate_next
        last = r.violation.trace[-1][0]
        assert not list(enumerate_next(m.next, m.ctx(), m.vars, last))

    def test_por_disabled_with_named_reason(self):
        # symtoy declares SYMMETRY: POR must refuse, run unreduced,
        # and say why
        ri = Explorer(load("symtoy", no_deadlock=True)).run()
        tel = obs.Telemetry()
        with obs.use(tel):
            r = Explorer(load("symtoy", no_deadlock=True),
                         por=True).run()
        assert (r.distinct, r.generated) == (ri.distinct, ri.generated)
        assert "SYMMETRY" in tel.gauges.get("por.disabled_reason", "")
        assert any("--por requested but reduction disabled" in w
                   for w in r.warnings)

    @pytest.mark.parametrize("scfg", [
        {"backend": "interp", "workers": 1},
        {"backend": "interp", "workers": 3},
        {"backend": "jax", "platform": "cpu"},
        {"backend": "jax", "platform": "cpu", "resident": True,
         "no_trace": True},
        {"backend": "jax", "platform": "cpu", "host_seen": True},
    ])
    def test_por_verdict_parity_across_engines(self, scfg):
        """--por through CheckSession: every engine config reports the
        SAME violation verdict its unreduced run reports.  Since ISSUE
        18 the jax configs run the ample mask INSIDE the fused device
        step (por.engine == "device"), not the interpreter demotion."""
        if scfg["backend"] == "jax":
            pytest.importorskip("jax")
        from jaxmc.session import CheckSession, SessionConfig
        spec = os.path.join(SPECS, "portoy.tla")
        cfgp = os.path.join(SPECS, "portoy_bad.cfg")
        base = CheckSession(SessionConfig(spec=spec, cfg=cfgp, **scfg))
        rb = base.explore()
        tel = obs.Telemetry()
        with obs.use(tel):
            s = CheckSession(SessionConfig(spec=spec, cfg=cfgp,
                                           por=True, **scfg))
            rp = s.explore()
        assert not rb.ok and not rp.ok
        assert rp.violation.kind == rb.violation.kind == "invariant"
        assert rp.distinct <= rb.distinct
        if not scfg.get("no_trace"):
            _replays(load("portoy", "portoy_bad"), rp.violation.trace)
        if scfg["backend"] == "jax":
            assert tel.gauges.get("por.engine") == "device"
            assert tel.gauges.get("por.device_masked_arms", 0) > 0
        elif scfg.get("workers", 1) > 1:
            assert tel.gauges.get("parallel.fallback_reason") == "por"

    def test_por_rides_the_job_signature(self):
        from jaxmc.session import SessionConfig
        from jaxmc.serve.protocol import build_config, job_signature
        spec = os.path.join(SPECS, "portoy.tla")
        cfgp = os.path.join(SPECS, "portoy_bad.cfg")
        a = job_signature(SessionConfig(spec=spec, cfg=cfgp))
        b = job_signature(SessionConfig(spec=spec, cfg=cfgp, por=True))
        assert a != b  # reduced and unreduced runs are different jobs
        cfg = build_config(spec, cfgp, {"por": True})
        assert cfg.por is True


@pytest.mark.chaos
@pytest.mark.slow
class TestPORChaos:
    def test_sigkill_midrun_por_resume_parity(self, tmp_path):
        """SIGKILL a --por run mid-level; the resumed --por run must
        finish with counts identical to an uninterrupted --por run
        (the ample choice is a deterministic function of the seen
        set, which the checkpoint preserves)."""
        spec = os.path.join(SPECS, "portoy.tla")
        args = [spec, "--cfg", os.path.join(SPECS, "portoy_ok.cfg"),
                "--no-deadlock", "--por"]

        def cli(extra, env_extra=None):
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       **(env_extra or {}))
            return subprocess.run(
                [sys.executable, "-m", "jaxmc", "check"] + args + extra,
                capture_output=True, text=True, cwd=REPO, env=env,
                timeout=300)

        clean = cli([])
        assert clean.returncode == 0, clean.stderr
        ck = str(tmp_path / "por.ck")
        killed = cli(["--checkpoint", ck, "--checkpoint-every", "0",
                      "--quiet"],
                     {"JAXMC_FAULTS": "run_kill:level=3"})
        assert killed.returncode in (-9, 137), killed.stderr
        assert os.path.exists(ck), "no checkpoint survived the kill"
        resumed = cli(["--resume", ck])
        assert resumed.returncode == 0, resumed.stderr

        def counts(stdout):
            for line in stdout.splitlines():
                if "states generated," in line and \
                        "distinct states found" in line and \
                        "states/sec" in line:
                    parts = line.split()
                    return int(parts[0]), int(parts[3])
            raise AssertionError(f"no summary in:\n{stdout}")

        assert counts(resumed.stdout) == counts(clean.stdout)


# ------------------------------------------------- bounds-sized engines

class TestPredictedCapacityRung:
    def test_cold_resident_run_zero_growth_recompiles(self):
        """Acceptance: a fully-proven spec with NO saved capacity
        profile completes with zero in-window recompiles — the
        predicted rung sizes every bucket from the bounds fixpoint."""
        pytest.importorskip("jax")
        m = load("portoy", "portoy_ok", no_deadlock=True)
        r, tel = _device_run(m, resident=True, store_trace=False)
        assert r.ok and (r.generated, r.distinct) == (366, 150)
        assert tel.gauges.get("profile.predicted_states") == 432
        assert tel.gauges.get("profile.predicted_caps")
        fresh = [bool(lv.get("fresh_compile")) for lv in tel.levels]
        assert sum(fresh) == 1 and fresh[0], \
            f"growth recompiles on the predicted rung: {tel.levels}"

    def test_prediction_refused_when_unproven(self, monkeypatch):
        pytest.importorskip("jax")
        # transfer-style racing counters: no estimate, no prediction —
        # the ladder falls through to the platform defaults as before
        from jaxmc.backend.bfs import TpuExplorer
        tel = obs.Telemetry()
        with obs.use(tel):
            ex = TpuExplorer(load("viewtoy"), store_trace=False,
                             resident=True)
        assert tel.gauges.get("profile.predicted_states") == 15
        monkeypatch.setenv("JAXMC_PREDICT_MAX", "0")
        tel2 = obs.Telemetry()
        with obs.use(tel2):
            TpuExplorer(load("viewtoy"), store_trace=False,
                        resident=True)
        assert tel2.gauges.get("profile.predicted_states") is None

    def test_fast_lane_reads_widened_estimate(self):
        from jaxmc.session import SessionConfig, batch_profile
        prof = batch_profile(SessionConfig(
            spec=os.path.join(SPECS, "portoy.tla"),
            cfg=os.path.join(SPECS, "portoy_ok.cfg"),
            backend="jax", host_seen=True))
        # enum/bool/fun cardinalities now estimate specs the pure-int
        # rule refused: the serve fast lane gets a real cost bound
        assert prof is not None and prof.cost_estimate == 432
