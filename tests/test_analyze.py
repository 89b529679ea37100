r"""jaxmc.analyze — static bounds/type inference, demotion prediction,
and the corpus linter (ISSUE 9).

Layers:
  1. bounds inference soundness: the inferred per-variable summary must
     CONTAIN every integer observed in sampled reachable states, on the
     fixtures whose shapes span the lattice (viewtoy/symtoy/constoy/
     transfer_scaled);
  2. proven lanes: counts/traces bit-identical with inference on vs
     off, with `analyze.proven_lanes > 0` where inference converges and
     the previously guarded lanes gone;
  3. predicted demotions: interparm_toy's build-time-demoted arm is
     named BEFORE kernel construction, with the build path's exact
     reason string and zero futile builds;
  4. the linter: every diagnostic class on the linttoy fixture, the
     strict-mode exit-2 CLI contract, and the serve daemon rejecting a
     statically-broken submission with the diagnostics in the payload.
"""

import json
import os
import subprocess
import sys
import urllib.request

import pytest

from jaxmc.engine.explore import Explorer, format_trace
from jaxmc.front.cfg import parse_cfg
from jaxmc.sem.modules import Loader, bind_model
from jaxmc.sem.values import Fcn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = os.path.join(REPO, "specs")


def load(name, cfg=None):
    cfgp = os.path.join(SPECS, cfg or f"{name}.cfg")
    mod = Loader([SPECS]).load_path(os.path.join(SPECS, f"{name}.tla"))
    with open(cfgp) as fh:
        return bind_model(mod, parse_cfg(fh.read()))


def _ints_of(v, out):
    if isinstance(v, bool):
        return
    if isinstance(v, int):
        out.append(v)
    elif isinstance(v, (frozenset, set, tuple, list)):
        for x in v:
            _ints_of(x, out)
    elif isinstance(v, Fcn):
        for k, val in v.d.items():
            _ints_of(k, out)
            _ints_of(val, out)


# ------------------------------------------------------- bounds inference

@pytest.mark.parametrize("name", ["viewtoy", "symtoy", "constoy",
                                  "transfer_scaled"])
def test_inferred_bounds_contain_observed(name):
    """Soundness on real reachable states: every int component of every
    sampled state must sit inside the variable's inferred summary."""
    from jaxmc.analyze import infer_state_bounds
    from jaxmc.engine.simulate import sample_states

    model = load(name)
    rep = infer_state_bounds(model)
    assert rep is not None, "analysis bailed on a repo fixture"
    summaries = rep.summaries()
    sampled = sample_states(model, bfs_states=600, n_walks=30,
                            walk_depth=40)
    assert sampled, "sampler produced no states"
    for st in sampled:
        for var, val in st.items():
            ints = []
            _ints_of(val, ints)
            if not ints:
                continue
            assert var in summaries, \
                f"{name}.{var} holds ints but has no summary"
            s = summaries[var]
            for i in ints:
                assert (s.lo is None or i >= s.lo) and \
                    (s.hi is None or i <= s.hi), \
                    f"{name}.{var}: observed {i} outside inferred " \
                    f"[{s.lo}, {s.hi}]"


def test_inference_proves_expected_fixture_bounds():
    """The converged intervals on the hand-checkable fixtures."""
    from jaxmc.analyze import infer_state_bounds
    lanes = infer_state_bounds(load("viewtoy")).lane_bounds()
    assert lanes == {"x": (0, 4), "noise": (0, 2)}
    # constoy needs the x+y<=c CONSTRAINT refinement: successors of
    # constrained states reach 6
    lanes = infer_state_bounds(load("constoy")).lane_bounds()
    assert lanes == {"a": (0, 6), "b": (0, 6)}
    # transfer_scaled: money is Init-bounded and UNCHANGED everywhere;
    # alice/bob grow without a provable bound and must NOT be proven
    lanes = infer_state_bounds(load("transfer_scaled")).lane_bounds()
    assert lanes == {"money": (1, 12)}


def _device_run(name, env, **kw):
    from jaxmc import obs
    from jaxmc.backend.bfs import TpuExplorer
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    tel = obs.Telemetry()
    try:
        with obs.use(tel):
            ex = TpuExplorer(load(name), **kw)
            r = ex.run()
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return r, tel, ex


@pytest.mark.parametrize("name", ["viewtoy", "constoy", "symtoy"])
def test_proven_lanes_counts_and_traces_identical(name):
    """Inference on vs off: bit-identical counts/violations, proven
    lanes replace guarded lanes where the proof converges."""
    ri = Explorer(load(name)).run()
    ron, tel_on, _ = _device_run(name, {})
    roff, tel_off, _ = _device_run(name, {"JAXMC_ANALYZE_BOUNDS": "0"})
    for r in (ron, roff):
        assert (r.distinct, r.generated) == (ri.distinct, ri.generated)
        assert r.ok == ri.ok
    if ri.violation is not None:
        assert format_trace(ron.violation) == \
            format_trace(roff.violation) == format_trace(ri.violation)
    on_proven = tel_on.gauges.get("analyze.proven_lanes", 0)
    off_proven = tel_off.gauges.get("analyze.proven_lanes", 0)
    assert off_proven == 0
    if name in ("viewtoy", "constoy"):
        # both int lanes proven: the guarded (observed-range) count
        # drops to zero — no OV_PACK re-sample cycle is reachable
        assert on_proven == 2
        assert tel_on.gauges.get("layout.pack_guarded_lanes") == 0
        assert tel_off.gauges.get("layout.pack_guarded_lanes") == 2
        # proven widths pack TIGHTER than margin-widened sampling
        assert tel_on.gauges.get("layout.bits_per_state") < \
            tel_off.gauges.get("layout.bits_per_state")


# ---------------------------------------------------- demotion prediction

def test_predicted_demotion_matches_build_time_reason():
    """interparm_toy's Pick arm: predicted BEFORE kernel construction,
    zero futile build attempts, and the exact build-time reason string
    (the satellite's no-divergent-wording contract)."""
    from jaxmc import native_store
    if not native_store.is_available():
        pytest.skip("hybrid needs the native store")
    rp, telp, exp = _device_run("interparm_toy", {}, store_trace=False,
                                host_seen=True)
    rb, telb, exb = _device_run("interparm_toy",
                                {"JAXMC_ANALYZE_PREDICT": "0"},
                                store_trace=False, host_seen=True)
    # same demotion table, identical wording, on both paths
    assert [(a.label, w) for a, w in exp.fb_arms] == \
        [(a.label, w) for a, w in exb.fb_arms] == \
        [("Pick", "SUBSET of symbolic set")]
    assert exp.arm_verdicts and not exb.arm_verdicts
    assert telp.counters.get("analyze.predicted_demotions") == 1
    assert telp.gauges.get("analyze.arm_verdicts") == \
        {"Pick": "SUBSET of symbolic set"}
    # zero futile builds: only Bump's kernel was ever constructed on
    # the predicted path; the build path also pays Pick's attempt
    assert telp.counters.get("compile.kernels_built") == 1
    assert telb.counters.get("compile.kernels_built", 0) >= 2
    # verdicts change nothing about the answer
    assert (rp.distinct, rp.generated) == (rb.distinct, rb.generated) \
        == (19, 29)


def test_predictor_is_silent_on_compilable_fixtures():
    from jaxmc.analyze import predict_arm_demotions
    from jaxmc.compile.ground import split_arms
    for name in ("viewtoy", "constoy", "symtoy", "symtoy_scaled",
                 "viewtoy_scaled", "transfer_scaled", "symid"):
        model = load(name)
        assert predict_arm_demotions(model, split_arms(model)) == {}, \
            f"false demotion verdict on {name}"


def test_unroll_message_constant_matches_raise_site():
    """The predictor's recursion wording IS kernel2's raise wording."""
    from jaxmc.compile.kernel2 import unroll_limit_message
    msg = unroll_limit_message("Depth", 64)
    assert msg.startswith("recursive operator Depth exceeds the "
                          "compile-time unroll limit (64; raise with "
                          "JAXMC_OP_UNROLL_LIMIT)")


# -------------------------------------------------------------- linter

LINTTOY = os.path.join(SPECS, "linttoy.tla")
LINTTOY_CFG = os.path.join(SPECS, "linttoy.cfg")


def test_linttoy_fires_every_diagnostic_class():
    from jaxmc.analyze import lint_pair
    diags = lint_pair(LINTTOY, LINTTOY_CFG)
    codes = {d.code for d in diags}
    assert codes == {"JMC101", "JMC102", "JMC201", "JMC202", "JMC203",
                     "JMC301", "JMC302"}
    by_code = {d.code: d for d in diags}
    assert "Missing" in by_code["JMC101"].message
    assert by_code["JMC101"].severity == "error"
    assert "Ghost" in by_code["JMC102"].message
    assert "ghost" in by_code["JMC201"].message
    assert "Stuck" in by_code["JMC202"].message
    assert by_code["JMC202"].severity == "warning"
    assert "CHOOSE" in by_code["JMC203"].message
    assert "Orphan" in by_code["JMC301"].message
    assert by_code["JMC301"].severity == "info"
    # every diagnostic is located
    for d in diags:
        assert d.path and d.line, d.render()


def test_repo_corpus_pairs_lint_clean_modulo_waivers():
    """The satellite gate, in-process: repo-local manifest pairs stay
    clean except for explicitly waived codes."""
    from jaxmc.analyze import lint_pair
    from jaxmc.corpus import CASES
    for case in CASES:
        if case.root != "repo" or case.lint_only or case.includes:
            continue
        diags = lint_pair(case.spec_path(), case.cfg_path())
        unwaived = [d for d in diags if d.code not in case.lint_waive]
        assert not unwaived, \
            f"{case.spec}: {[d.render() for d in unwaived]}"


def test_lint_cli_exit_codes(tmp_path):
    from jaxmc.analyze.__main__ import main as analyze_main
    assert analyze_main(["lint", os.path.join(SPECS, "viewtoy.tla")]) \
        == 0
    assert analyze_main(["lint", LINTTOY, LINTTOY_CFG]) == 2
    # warnings only (no cfg errors): a copy whose cfg assigns Ghost
    # and names only defined invariants
    cfg2 = tmp_path / "linttoy.cfg"
    cfg2.write_text(
        "SPECIFICATION Spec\nINVARIANT TypeInv HazInv\n"
        "SYMMETRY Perms\nCONSTANTS\n  P = {a1, a2}\n  Limit = 4\n"
        "  Unused = 7\n  Ghost = 9\n")
    assert analyze_main(["lint", LINTTOY, str(cfg2)]) == 1
    assert analyze_main(["lint", LINTTOY, str(cfg2),
                         "--errors-only"]) == 0


def test_session_analyze_stage_and_strict_contract():
    from jaxmc.session import AnalyzeError, CheckSession, SessionConfig
    # clean pair: stage runs, no diagnostics, search unaffected
    sess = CheckSession(SessionConfig(
        spec=os.path.join(SPECS, "viewtoy.tla"), analyze="warn"))
    assert sess.analyze() == []
    res = sess.explore()
    assert (res.distinct, res.generated) == (5, 11)
    # broken pair under strict: AnalyzeError BEFORE any engine exists
    sess2 = CheckSession(SessionConfig(
        spec=LINTTOY, cfg=LINTTOY_CFG, analyze="strict"))
    with pytest.raises(AnalyzeError) as ei:
        sess2.analyze()
    assert {d.code for d in ei.value.diagnostics} >= \
        {"JMC101", "JMC102"}
    assert sess2.engine is None
    # the strict refusal HOLDS: a driver that caught the first error
    # cannot stage-chain past it — every later analyze() re-raises
    with pytest.raises(AnalyzeError):
        sess2.analyze()
    assert sess2.engine is None


def test_check_cli_strict_exit2_subprocess():
    """The CLI contract: --analyze=strict exits 2 with the diagnostics
    on stderr, --analyze=off preserves the old behavior."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run(
        [sys.executable, "-m", "jaxmc", "check", LINTTOY,
         "--cfg", LINTTOY_CFG, "--analyze", "strict"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert p.returncode == 2
    assert "JMC101" in p.stderr and "JMC202" in p.stderr
    assert "--analyze=strict refused the run" in p.stderr
    # a typo'd JAXMC_ANALYZE env default must fail loudly, never
    # silently degrade the gate to warn
    bad = subprocess.run(
        [sys.executable, "-m", "jaxmc", "check",
         os.path.join(SPECS, "viewtoy.tla")],
        capture_output=True, text=True, cwd=REPO, timeout=120,
        env=dict(env, JAXMC_ANALYZE="stirct"))
    assert bad.returncode == 2
    assert "invalid --analyze/JAXMC_ANALYZE" in bad.stderr
    # warn on a clean spec: identical stdout to --analyze=off (modulo
    # the wall-clock/rate numbers in the summary line)
    import re
    outs = {}
    for mode in ("off", "warn"):
        q = subprocess.run(
            [sys.executable, "-m", "jaxmc", "check",
             os.path.join(SPECS, "viewtoy.tla"), "--quiet",
             "--analyze", mode],
            capture_output=True, text=True, env=env, cwd=REPO,
            timeout=120)
        assert q.returncode == 0
        outs[mode] = re.sub(r"\(\d+ states/sec[^)]*\)", "(RATE)",
                            q.stdout)
    assert outs["off"] == outs["warn"]


# ---------------------------------------------------------- serve gate

def test_serve_rejects_statically_broken_job():
    """Submit-time rejection e2e: the daemon refuses the job with the
    diagnostics in the 400 payload, before any worker touches it."""
    import tempfile

    from jaxmc import drain
    from jaxmc.serve import ServeDaemon
    from jaxmc.serve.protocol import BadJob

    drain.clear()
    with tempfile.TemporaryDirectory() as spool:
        d = ServeDaemon(spool=spool, workers=1, quiet=True).start()
        try:
            # in-process surface
            with pytest.raises(BadJob) as ei:
                d.submit({"spec": LINTTOY, "cfg": LINTTOY_CFG})
            assert "JMC101" in str(ei.value)
            assert d.tel.counters.get("serve.jobs_rejected") == 1
            # HTTP surface: 400 with the diagnostic in the payload
            req = urllib.request.Request(
                f"http://{d.host}:{d.port}/jobs",
                data=json.dumps({"spec": LINTTOY,
                                 "cfg": LINTTOY_CFG}).encode(),
                headers={"Content-Type": "application/json"})
            try:
                urllib.request.urlopen(req, timeout=30)
                raise AssertionError("expected HTTP 400")
            except urllib.error.HTTPError as he:
                assert he.code == 400
                payload = json.loads(he.read().decode())
                assert "JMC101" in payload["error"]
            # a clean job still queues fine afterwards
            job = d.submit({"spec": os.path.join(SPECS, "viewtoy.tla"),
                            "options": {"max_states": 50}})
            assert job["id"]
        finally:
            d.initiate_drain("test done")
            d.shutdown()
    drain.clear()
