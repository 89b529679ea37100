r"""Interpreter + engine tests: evaluator semantics, enumeration, and the
corpus oracle runs recorded in the reference (SURVEY.md §6).
"""

import os

import pytest

from jaxmc.front.parser import parse_expr_text
from jaxmc.front.cfg import CfgModelValue, ModelConfig, parse_cfg
from jaxmc.sem.values import Fcn, ModelValue, fmt, mk_seq
from jaxmc.sem.eval import Ctx, eval_expr
from jaxmc.sem.modules import Loader, bind_model, BASE_IDENTS
from jaxmc.sem.enumerate import enumerate_init, enumerate_next
from jaxmc.engine.explore import Explorer, format_trace

from conftest import REFERENCE, needs_reference

SPECS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "specs")


def ev(src, **bound):
    ctx = Ctx(dict(BASE_IDENTS), bound=bound)
    return eval_expr(parse_expr_text(src), ctx)


class TestEval:
    def test_arith(self):
        assert ev("2 + 3 * 4") == 14
        assert ev("7 \\div 2") == 3
        assert ev("7 % 2") == 1
        assert ev("2 ^ 10") == 1024
        assert ev("-(5) + 1") == -4

    def test_sets(self):
        assert ev("1 .. 3") == frozenset({1, 2, 3})
        assert ev("{1, 2} \\cup {2, 3}") == frozenset({1, 2, 3})
        assert ev("{x \\in 1..10 : x % 2 = 0}") == frozenset({2, 4, 6, 8, 10})
        assert ev("{x * x : x \\in 1..3}") == frozenset({1, 4, 9})
        assert ev("Cardinality(SUBSET (1..3))") == 8
        assert ev("UNION {{1}, {2, 3}}") == frozenset({1, 2, 3})
        assert ev("{1} \\subseteq {1, 2}") is True
        assert ev("1 \\in Nat") is True
        assert ev("-1 \\in Nat") is False
        assert ev("-1 \\in Int") is True

    def test_bool_int_distinct(self):
        assert ev("TRUE \\in {1, 2}") is False
        assert ev("1 \\in {TRUE, FALSE}") is False

    def test_functions(self):
        assert ev("[x \\in 1..3 |-> x * 2][2]") == 4
        assert ev("DOMAIN [x \\in 1..3 |-> x]") == frozenset({1, 2, 3})
        assert ev('[a |-> 1, b |-> 2].b') == 2
        assert ev("[f EXCEPT ![2] = @ + 10][2]",
                  f=Fcn({1: 1, 2: 2})) == 12
        assert ev("Cardinality([b: {0, 1}, c: {0, 1}])") == 4
        assert ev("Cardinality([{1, 2} -> {1, 2, 3}])") == 9
        assert ev("(1 :> 2 @@ 3 :> 4)[3]") == 4

    def test_sequences(self):
        assert ev("Len(<<1, 2, 3>>)") == 3
        assert ev("Append(<<1>>, 2)") == mk_seq([1, 2])
        assert ev("Head(<<1, 2>>)") == 1
        assert ev("Tail(<<1, 2>>)") == mk_seq([2])
        assert ev("<<1, 2>> \\o <<3>>") == mk_seq([1, 2, 3])
        assert ev("SubSeq(<<1, 2, 3, 4>>, 2, 3)") == mk_seq([2, 3])
        assert ev("<<1, 2>> \\in Seq(Nat)") is True
        # a sequence IS the function with domain 1..n
        assert ev("<<4, 5>> = [i \\in 1..2 |-> i + 3]") is True

    def test_quantifiers_choose(self):
        assert ev("\\A x \\in 1..5 : x < 6") is True
        assert ev("\\E x \\in 1..5 : x = 3") is True
        assert ev("CHOOSE x \\in 1..5 : x * x = 9") == 3
        # deterministic lowest witness
        assert ev("CHOOSE x \\in 1..5 : x > 2") == 3

    def test_if_case_let(self):
        assert ev("IF 1 < 2 THEN 10 ELSE 20") == 10
        assert ev("CASE 1 > 2 -> 0 [] 2 > 1 -> 5 [] OTHER -> 9") == 5
        assert ev("LET sq(x) == x * x IN sq(7)") == 49
        assert ev("LET a == 3 b == a + 1 IN a * b") == 12

    def test_recursive_let(self):
        assert ev("LET RECURSIVE f(_) f(n) == IF n = 0 THEN 1 "
                  "ELSE n * f(n - 1) IN f(5)") == 120

    def test_recursive_fn_constructor(self):
        assert ev("LET f[n \\in 0..5] == IF n = 0 THEN 1 ELSE n * f[n - 1] "
                  "IN f[5]") == 120

    def test_tuples_products(self):
        assert ev("Cardinality({1, 2} \\X {3, 4} \\X {5})") == 4
        v = ev("CHOOSE <<a, b>> \\in {1} \\X {2} : TRUE")
        assert v == mk_seq([1, 2])

    def test_strings_model_values(self):
        assert ev('"abc" = "abc"') is True
        assert ev('"abc" \\in STRING') is True


def run_spec(path, cfg=None, **kw):
    ldr = Loader([os.path.dirname(os.path.abspath(path))])
    m = ldr.load_path(path)
    model = bind_model(m, cfg or ModelConfig(specification="Spec"))
    return Explorer(model, **kw).run()


class TestEngine:
    @needs_reference
    def test_atomic_add(self):
        r = run_spec(os.path.join(REFERENCE, "atomic_add.tla"))
        assert r.ok
        assert r.distinct == 5
        assert r.generated == 7

    @needs_reference
    def test_pcal_intro_fixed_passes(self):
        cfg = parse_cfg(open(os.path.join(REFERENCE, "pcal_intro.cfg")).read())
        r = run_spec(os.path.join(REFERENCE, "pcal_intro.tla"), cfg)
        assert r.ok
        assert r.distinct == 3800
        assert r.generated == 5850

    def test_pcal_intro_buggy_matches_tlc_oracle(self):
        # the recorded TLC run: 9097 generated / 6164 distinct at the
        # assertion violation (/root/reference/README.md:319-320)
        r = run_spec(os.path.join(SPECS, "pcal_intro_buggy.tla"))
        assert not r.ok
        assert r.violation.kind == "assert"
        assert r.generated == 9097
        assert r.distinct == 6164
        assert len(r.violation.trace) == 6
        # README's trace: both at Transfer, money <<1, 10>>
        st0 = r.violation.trace[0][0]
        assert fmt(st0["money"]) == "<<1, 10>>"
        assert fmt(st0["pc"]) == '<<"Transfer", "Transfer">>'

    def test_buggy_invariant_violation_found(self):
        cfg = ModelConfig(specification="Spec",
                          invariants=["MoneyInvariant"])
        r = run_spec(os.path.join(SPECS, "pcal_intro_buggy.tla"), cfg)
        assert not r.ok and r.violation.kind == "invariant"
        assert r.violation.name == "MoneyInvariant"

    def test_trace_labels(self):
        r = run_spec(os.path.join(SPECS, "pcal_intro_buggy.tla"))
        labels = [lbl for _, lbl in r.violation.trace]
        assert labels[0] == "Initial predicate"
        assert labels[1].startswith("Transfer(")

    def test_deadlock_detection(self):
        # two processes that each await the other's increment never fire
        import tempfile
        src = """---- MODULE dl ----
EXTENDS Naturals
VARIABLES x, y
Init == x = 0 /\\ y = 0
Next == \\/ x > 0 /\\ y' = y + 1 /\\ x' = x
        \\/ y > 0 /\\ x' = x + 1 /\\ y' = y
====
"""
        with tempfile.NamedTemporaryFile("w", suffix=".tla",
                                         delete=False) as f:
            f.write(src)
            p = f.name
        cfg = ModelConfig(init="Init", next="Next")
        r = run_spec(p, cfg)
        assert not r.ok and r.violation.kind == "deadlock"
        cfg2 = ModelConfig(init="Init", next="Next", check_deadlock=False)
        r2 = run_spec(p, cfg2)
        assert r2.ok
        os.unlink(p)


class TestHourClock:
    @needs_reference
    def test_hourclock(self):
        d = os.path.join(REFERENCE, "examples/SpecifyingSystems/HourClock")
        cfg = parse_cfg(open(os.path.join(d, "HourClock.cfg")).read())
        r = run_spec(os.path.join(d, "HourClock.tla"), cfg)
        assert r.ok
        assert r.distinct == 12


class TestPcalSemantics:
    def test_sequential_assignment_reads_updated_value(self):
        # PlusCal statements in one step execute sequentially: `x := 1; y := x`
        # must set y to the NEW x (p-manual semantics; review finding repro)
        import tempfile
        src = """---- MODULE seqassign ----
EXTENDS Naturals, TLC
(* --algorithm seqassign
variables x = 0, y = 0
process P \\in {1}
begin
Step:
  x := 1;
  y := x;
  assert y = 1;
end process
end algorithm *)
====
"""
        with tempfile.NamedTemporaryFile("w", suffix=".tla",
                                         delete=False) as f:
            f.write(src)
            p = f.name
        r = run_spec(p, ModelConfig(specification="Spec"))
        os.unlink(p)
        assert r.ok

    def test_while_loop(self):
        import tempfile
        src = """---- MODULE wl ----
EXTENDS Naturals, TLC
(* --algorithm wl
variables total = 0
process P \\in {1}
  variables i = 0;
begin
Loop:
  while i < 3 do
    total := total + 1;
    i := i + 1;
  end while;
Done1: assert total = 3;
end process
end algorithm *)
====
"""
        with tempfile.NamedTemporaryFile("w", suffix=".tla",
                                         delete=False) as f:
            f.write(src)
            p = f.name
        r = run_spec(p, ModelConfig(specification="Spec"))
        os.unlink(p)
        assert r.ok


class TestRefinement:
    @needs_reference
    def test_paxos_voting_refinement_checked(self):
        # MCPaxos.cfg PROPERTY VotingSpecBar == V!Spec — the Paxos -> Voting
        # refinement (SURVEY.md §3.4) holds stepwise on every edge
        d = os.path.join(REFERENCE, "examples/Paxos")
        cfg = parse_cfg(open(os.path.join(d, "MCPaxos.cfg")).read())
        r = run_spec(os.path.join(d, "MCPaxos.tla"), cfg)
        assert r.ok
        assert not any("VotingSpecBar" in w for w in r.warnings)

    @needs_reference
    def test_hourclock2_equivalence_checked(self):
        d = os.path.join(REFERENCE, "examples/SpecifyingSystems/HourClock")
        cfg = parse_cfg(open(os.path.join(d, "HourClock2.cfg")).read())
        r = run_spec(os.path.join(d, "HourClock2.tla"), cfg)
        assert r.ok and not r.warnings

    def test_non_refinement_detected(self):
        import tempfile
        src = """---- MODULE badhc ----
EXTENDS Naturals
VARIABLE hr
HCini == hr \\in 1..12
HCnxt == hr' = IF hr >= 11 THEN 1 ELSE hr + 2
HC == HCini /\\ [][HCnxt]_hr
Jump == hr' = IF hr = 12 THEN 1 ELSE hr + 1
JumpSpec == HCini /\\ [][Jump]_hr
====
"""
        with tempfile.NamedTemporaryFile("w", suffix=".tla",
                                         delete=False) as f:
            f.write(src)
            p = f.name
        cfg = ModelConfig(specification="HC", properties=["JumpSpec"],
                          check_deadlock=False)
        r = run_spec(p, cfg)
        os.unlink(p)
        assert not r.ok
        assert r.violation.kind == "property"
        assert r.violation.name == "JumpSpec"

    @needs_reference
    def test_liveness_property_checked_with_refinement(self):
        # MCAlternatingBit.cfg checks ABCSpec (refinement, stepwise, plus
        # its ABCFairness half over the behavior graph — r3) and
        # SentLeadsToRcvd (a ~> property, behavior-graph liveness) in one
        # model — ALL halves genuinely checked, zero warnings
        d = os.path.join(REFERENCE, "examples/SpecifyingSystems/TLC")
        cfg = parse_cfg(open(os.path.join(d, "MCAlternatingBit.cfg")).read())
        r = run_spec(os.path.join(d, "MCAlternatingBit.tla"), cfg)
        assert r.ok
        assert not any("NOT checked" in w for w in r.warnings), r.warnings

    @needs_reference
    def test_abcspec_fairness_half_violated_without_spec_fairness(self):
        # negative control for the adopted fairness half: under the
        # fairness-free INIT/NEXT spec a behavior may stutter forever
        # with CRcvMsg enabled, violating ABCFairness's WF_cvars(CRcvMsg)
        # (ABCorrectness.tla:37-39) — the abstract action must classify
        # concrete edges relationally for this to be non-vacuous
        d = os.path.join(REFERENCE, "examples/SpecifyingSystems/TLC")
        cfg = parse_cfg(
            "INIT ABInit\nNEXT ABNext\nCONSTANTS\n  Data = {d1, d2}\n"
            "  msgQLen = 2\n  ackQLen = 2\nCONSTRAINT SeqConstraint\n"
            "PROPERTY ABCSpec\nCHECK_DEADLOCK FALSE\n")
        r = run_spec(os.path.join(d, "MCAlternatingBit.tla"), cfg)
        assert not r.ok
        assert r.violation.kind == "property"
        assert "ABCSpec" in r.violation.name


class TestCheckpoint:
    @needs_reference
    def test_checkpoint_resume_roundtrip(self):
        # truncated run writes a checkpoint; resuming completes with the
        # exact full-run counts (TLC's states/ dir contract, SURVEY.md §5)
        import tempfile
        spec = os.path.join(REFERENCE, "pcal_intro.tla")
        cfg = parse_cfg(open(os.path.join(REFERENCE, "pcal_intro.cfg")).read())
        ckpt = tempfile.mktemp(suffix=".ckpt")
        m1 = Loader([]).load_path(spec)
        r1 = Explorer(bind_model(m1, cfg), max_states=1500,
                      checkpoint_path=ckpt, checkpoint_every=0.0).run()
        assert r1.truncated and os.path.exists(ckpt)
        m2 = Loader([]).load_path(spec)
        r2 = Explorer(bind_model(m2, cfg), resume_from=ckpt).run()
        os.unlink(ckpt)
        assert r2.ok
        assert r2.distinct == 3800
        assert r2.generated == 5850

    def test_checkpoint_resume_with_symmetry(self, tmp_path):
        # the resumed seen-set must be rebuilt with symmetry-canonical
        # keys, or known states get re-added after resume (inflated counts)
        spec = tmp_path / "symm.tla"
        spec.write_text(TestSymmetry.SYMM)
        ckpt = str(tmp_path / "symm.ckpt")

        def model():
            cfg = ModelConfig(init="Init", next="Next", check_deadlock=False,
                              symmetry="Sym")
            cfg.constants["Proc"] = frozenset(
                {CfgModelValue("p1"), CfgModelValue("p2")})
            return bind_model(Loader([]).load_path(str(spec)), cfg)

        r1 = Explorer(model(), max_states=3, checkpoint_path=ckpt,
                      checkpoint_every=0.0).run()
        assert r1.truncated and os.path.exists(ckpt)
        r2 = Explorer(model(), resume_from=ckpt).run()
        assert r2.ok
        assert r2.distinct == 6   # == the unresumed symmetric run

    @needs_reference
    def test_checkpoint_resume_cross_process(self, tmp_path):
        # checkpoints must survive a process boundary: str/frozenset hashes
        # are per-process, so pickled values must not carry cached hashes,
        # and interned ModelValues must re-intern (MCPaxos states hold both)
        import subprocess
        import sys
        ckpt = str(tmp_path / "mcpaxos.ckpt")
        d = os.path.join(REFERENCE, "examples/Paxos")
        base = [sys.executable, "-m", "jaxmc", "check",
                os.path.join(d, "MCPaxos.tla"),
                "--cfg", os.path.join(d, "MCPaxos.cfg")]
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__)))}
        r1 = subprocess.run(base + ["--max-states", "10", "--checkpoint",
                                    ckpt, "--checkpoint-every", "0"],
                            capture_output=True, text=True, env=env)
        assert "TRUNCATED" in r1.stdout, r1.stdout + r1.stderr
        r2 = subprocess.run(base + ["--resume", ckpt],
                            capture_output=True, text=True, env=env)
        # exact full-run counts (the pinned unresumed run: 82/25)
        assert "82 states generated, 25 distinct" in r2.stdout, \
            r2.stdout + r2.stderr
        assert "No error has been found" in r2.stdout


class TestSimulate:
    def test_simulate_finds_assert(self):
        from jaxmc.engine.simulate import random_walks
        model = bind_model(
            Loader([]).load_path(os.path.join(SPECS, "pcal_intro_buggy.tla")),
            ModelConfig(specification="Spec"))
        v = random_walks(model, n_walks=80, depth=12, seed=3,
                         check_invariants=True)
        assert v is not None and v.kind == "assert"

    @needs_reference
    def test_simulate_clean_spec_passes(self):
        from jaxmc.engine.simulate import random_walks
        cfg = parse_cfg(open(os.path.join(REFERENCE, "pcal_intro.cfg")).read())
        model = bind_model(
            Loader([]).load_path(os.path.join(REFERENCE, "pcal_intro.tla")),
            cfg)
        v = random_walks(model, n_walks=25, depth=15, seed=1,
                         check_invariants=True)
        assert v is None


class TestSymmetry:
    SYMM = """---- MODULE symm ----
EXTENDS Naturals, FiniteSets, TLC
CONSTANTS Proc
VARIABLE x
Init == x = [p \\in Proc |-> 0]
Bump(p) == x[p] < 2 /\\ x' = [x EXCEPT ![p] = x[p] + 1]
Next == \\E p \\in Proc : Bump(p)
Sym == Permutations(Proc)
====
"""

    def _model(self, symmetry):
        import tempfile
        with tempfile.NamedTemporaryFile("w", suffix=".tla",
                                         delete=False) as f:
            f.write(self.SYMM)
            p = f.name
        cfg = ModelConfig(init="Init", next="Next", check_deadlock=False,
                          symmetry=symmetry)
        cfg.constants["Proc"] = frozenset(
            {CfgModelValue("p1"), CfgModelValue("p2")})
        m = bind_model(Loader([]).load_path(p), cfg)
        os.unlink(p)
        return m

    def test_symmetry_collapses_orbit(self):
        # 3x3 counter grid collapses to unordered pairs under p1<->p2
        r_full = Explorer(self._model(None)).run()
        r_sym = Explorer(self._model("Sym")).run()
        assert r_full.distinct == 9
        assert r_sym.distinct == 6

    @needs_reference
    def test_mcpaxos_symmetry_cfg_unchanged(self):
        # MCPaxos's SYMMETRY over singleton sets is the identity
        d = os.path.join(REFERENCE, "examples/Paxos")
        cfg = parse_cfg(open(os.path.join(d, "MCPaxos.cfg")).read())
        r = run_spec(os.path.join(d, "MCPaxos.tla"), cfg)
        assert r.ok and r.distinct == 25


VIEWTOY = """---- MODULE viewtoy ----
EXTENDS Naturals
VARIABLES x, noise
Init == x = 0 /\\ noise = 0
Next == x' = (x + 1) % 3 /\\ noise' = 1 - noise
Spec == Init /\\ [][Next]_<<x, noise>>
MyView == x
ParamView(y) == y
AlwaysX1 == []<>(x = 1)
TypeInv == x \\in 0..2 /\\ noise \\in 0..1
====
"""


class TestView:
    """cfg VIEW (ConfigFileGrammar.tla:8-11; VERDICT r2 #8): states
    deduplicate by the view expression's VALUE — implemented on the
    interp and, since ISSUE 6, compiled on the jax backends (the dedup
    keys on the view's value lanes)."""

    def _model(self, tmp_path, with_view):
        spec = tmp_path / "viewtoy.tla"
        spec.write_text(VIEWTOY)
        cfg = parse_cfg("SPECIFICATION Spec\nINVARIANT TypeInv\n"
                        + ("VIEW MyView\n" if with_view else "")
                        + "CHECK_DEADLOCK FALSE\n")
        m = Loader([str(tmp_path)]).load_path(str(spec))
        return bind_model(m, cfg)

    def test_view_collapses_state_space(self, tmp_path):
        r_full = Explorer(self._model(tmp_path, False)).run()
        r_view = Explorer(self._model(tmp_path, True)).run()
        assert r_full.ok and r_view.ok
        # without VIEW: (x, noise) pairs; with VIEW x: one state per x
        assert r_full.distinct == 6
        assert r_view.distinct == 3

    def test_view_compiles_on_jax_backend(self, tmp_path):
        # ISSUE 6: cfg VIEW compiles — the device dedup keys on the
        # view's value lanes, matching the interp's collapsed counts
        from jaxmc.backend.bfs import TpuExplorer
        ri = Explorer(self._model(tmp_path, True)).run()
        ex = TpuExplorer(self._model(tmp_path, True), store_trace=True)
        assert ex.view_fn is not None
        r = ex.run()
        assert (r.generated, r.distinct, r.ok) == \
            (ri.generated, ri.distinct, ri.ok)
        assert r.distinct == 3  # one state per value of x

    def test_parameterized_view_rejected_at_bind(self, tmp_path):
        # TLC rejects parameterized views at config time; we must too
        # (review r3: it otherwise crashes on the unhashable closure)
        from jaxmc.sem.eval import EvalError
        spec = tmp_path / "viewtoy.tla"
        spec.write_text(VIEWTOY)
        cfg = parse_cfg("SPECIFICATION Spec\nVIEW ParamView\n")
        with pytest.raises(EvalError, match="parameters"):
            bind_model(Loader([str(tmp_path)]).load_path(str(spec)), cfg)

    def test_view_with_liveness_warns_not_checked(self, tmp_path):
        # liveness over the view-collapsed graph would be WRONG (false
        # violations reproduced in review r3); the obligations must be
        # dropped with an explicit warning, and no bogus violation
        spec = tmp_path / "viewtoy.tla"
        spec.write_text(VIEWTOY)
        cfg = parse_cfg("SPECIFICATION Spec\nPROPERTY AlwaysX1\n"
                        "VIEW MyView\nCHECK_DEADLOCK FALSE\n")
        m = Loader([str(tmp_path)]).load_path(str(spec))
        r = Explorer(bind_model(m, cfg)).run()
        assert r.ok
        assert any("VIEW" in w and "NOT checked" in w for w in r.warnings)

    def test_unknown_view_name_errors(self, tmp_path):
        from jaxmc.sem.eval import EvalError
        spec = tmp_path / "viewtoy.tla"
        spec.write_text(VIEWTOY)
        cfg = parse_cfg("SPECIFICATION Spec\nVIEW NoSuchDef\n")
        with pytest.raises(EvalError, match="NoSuchDef"):
            bind_model(Loader([str(tmp_path)]).load_path(str(spec)), cfg)


def test_bool_int_set_mix_raises():
    # TLC comparability semantics: {TRUE, 1} is an error, not a
    # 1-element set (the True == 1 deviation documented in sem/values.py)
    from jaxmc.sem.eval import EvalError
    ctx = Ctx({})
    with pytest.raises(EvalError, match="BOOLEAN and integer"):
        eval_expr(parse_expr_text("{TRUE, 1}"), ctx)
    # homogeneous sets still work
    assert eval_expr(parse_expr_text("{TRUE, FALSE}"), ctx) == \
        frozenset({True, False})
    assert eval_expr(parse_expr_text("{0, 1}"), ctx) == frozenset({0, 1})


def test_bool_int_setop_operand_mix_raises():
    # advisor r3: \cap and \ operand mixes must raise like \cup does —
    # {TRUE} \cap {1} is a comparability error in TLC, not {1}
    from jaxmc.sem.eval import EvalError
    for src in (r"{TRUE} \cap {1}", r"{TRUE} \ {1}", r"{1} \cap {TRUE}",
                r"{FALSE} \cup {0}"):
        with pytest.raises(EvalError, match="BOOLEAN and integer"):
            ev(src)
    # disjoint same-kind operands still fine
    assert ev(r"{TRUE} \cap {FALSE}") == frozenset()
    assert ev(r"{1} \ {0}") == frozenset({1})


def test_nested_bool_int_collapse_raises():
    # r4: NESTED True==1 conflations raise instead of silently collapsing
    # ({{TRUE}, {1}} used to dedup to a 1-element set; TLC raises when it
    # compares the inner TRUE with 1)
    from jaxmc.sem.eval import EvalError
    with pytest.raises(EvalError, match="BOOLEAN vs integer"):
        ev("{{TRUE}, {1}}")
    with pytest.raises(EvalError, match="BOOLEAN vs integer"):
        ev("{{0}, {FALSE}}")
    with pytest.raises(EvalError, match="BOOLEAN vs integer"):
        ev("{{TRUE}} = {{1}}")
    with pytest.raises(EvalError, match="BOOLEAN vs integer"):
        ev("<<TRUE>> = <<1>>")
    with pytest.raises(EvalError, match="BOOLEAN vs integer"):
        ev("{1} \\in {{TRUE}}")
    with pytest.raises(EvalError, match="BOOLEAN vs integer"):
        ev("[a |-> TRUE] = [a |-> 1]")
    # no false positives: genuinely equal / unequal nested values
    assert ev("{{TRUE}} = {{TRUE}}") is True
    assert ev("{{1}} = {{1}}") is True
    assert ev("{{TRUE}, {FALSE}} = {{FALSE}, {TRUE}}") is True
    assert ev("<<1, TRUE>> = <<1, TRUE>>") is True
    assert ev("{1} \\in {{1}, {2}}") is True
    assert ev("Cardinality({{0}, {1}})") == 2


def test_recfcn_bool_collapse_detected():
    # r5 regression (code-review find): the _has_bool cache must force a
    # lazy RecFcn before scanning — probing membership FIRST (which scans
    # the then-empty memo dict) must not cache a stale False that lets a
    # later TRUE-vs-1 equality slip through silently
    from jaxmc.sem.eval import RecFcn
    from jaxmc.sem.values import tla_eq, in_set, Fcn, EvalError
    f = RecFcn([1], lambda a: True)  # f = [x \in {1} |-> TRUE], lazy
    in_set(f, frozenset({Fcn({1: 2})}))  # scans f before it is forced
    with pytest.raises(EvalError, match="BOOLEAN vs integer"):
        tla_eq(f, Fcn({1: 1}))
    g = RecFcn([1], lambda a: True)
    with pytest.raises(EvalError, match="BOOLEAN vs integer"):
        tla_eq(g, Fcn({1: 1}))
