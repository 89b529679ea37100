r"""Counterexample traces on the resident engine (ISSUE 44).

`check --resident` without `--no-trace` keeps a state log on the device —
each level's new frontier rows, appended inside the resident loop — and, at a
violation, one more dispatch walks it back by re-expansion
(`bfs._make_trace_walk`).  Held here, on XLA:CPU at small sizes:

* the trace is a behaviour by the benchmark's plain reference
  (`bench/reference/transfer_violation.py::check_trace`), has the level
  engine's length, and the counts at the violation are the reference's whole
  levels; a deadlock and an assert return traces too;
* the judge itself: a behaviour with a step swapped or its last state
  replaced FAILS;
* with `no_trace` the program is the untraced one: the same signature as an
  engine that never heard of traces, ten operands, no log;
* a log too small GROWS by name and the trace is whole;
* SYMMETRY, VIEW, POR and `--seen-cap` yield traces that replay on the
  interpreter; `--resume` gives one up and says so by name.
"""

import importlib.util
import os
import shutil

import pytest

from jaxmc import obs
from jaxmc.session import CheckSession, SessionConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = os.path.join(REPO, "specs")
VIOLATION = os.path.join(REPO, "bench", "specs", "transfer_violation.tla")
CAPS = {"SC": 4096, "FCap": 1024, "AccCap": 2048, "VC": 256}
TRACE_NAMES = ("search.trace_len", "search.trace_rows_expanded",
               "search.log_rows", "search.log_bytes")


@pytest.fixture(autouse=True)
def _no_capacity_profiles(monkeypatch):
    monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "plain_reference_violation", os.path.join(
            REPO, "bench", "reference", "transfer_violation.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(tmp_path, procs, max_money,
         invariants=("AliceBounded", "NoMoneyCreated")):
    cfg = tmp_path / "v.cfg"
    cfg.write_text("SPECIFICATION Spec\nINVARIANTS %s\nCONSTANTS\n"
                   "  Procs = {%s}\n  MaxMoney = %d\n"
                   % (" ".join(invariants), ", ".join(procs), max_money))
    return str(cfg)


def _check(spec, cfg, tel=None, **opts):
    """The normal path: a CheckSession on XLA:CPU.  (result, session)"""
    opts = dict(dict(backend="jax", platform="cpu", chunk=64), **opts)
    tel = tel or obs.Telemetry(meta={})
    with obs.use(tel):
        sess = CheckSession(SessionConfig(spec=spec, cfg=cfg, **opts),
                            tel=tel)
        return sess.explore(), sess


def _plain(trace):
    """(states, labels) of a violation's trace, for the reference."""
    return ([{var: ({str(k): v for k, v in val.d.items()}
                    if hasattr(val, "d") else val)
              for var, val in st.items()} for st, _ in trace],
            [label for _, label in trace])


def _replays(model, trace):
    """A behaviour by the interpreter: the head an initial state, every
    step a transition of Next under the label the trace gives it."""
    from jaxmc.sem.enumerate import (enumerate_init, enumerate_next,
                                     label_str)
    ctx = model.ctx()
    assert trace[0][1] == "Initial predicate"
    assert trace[0][0] in enumerate_init(model.init, ctx, model.vars)
    for (st, _), (succ, label) in zip(trace, trace[1:]):
        steps = []
        try:
            for s2, lbl in enumerate_next(model.next, ctx, model.vars, st):
                steps.append((s2, lbl))
        except Exception:  # noqa: BLE001 — an Assert fires mid-expansion
            pass
        assert succ in [s for s, _ in steps]
        assert label in [label_str(lbl) for s, lbl in steps if s == succ]


# ---------------------------------------------- the cell's model, small

@pytest.mark.parametrize("procs,max_money", [
    (("p1", "p2"), 3), (("p1", "p2", "p3"), 4),
    (("p3", "p1", "p2"), 3)], ids=["2x3", "3x4", "3x3-permuted"])
def test_resident_trace_is_a_shortest_behaviour(tmp_path, reference, procs,
                                                max_money):
    cfg = _cfg(tmp_path, procs, max_money)
    res, _ = _check(VIOLATION, cfg, resident=True)
    lvl, _ = _check(VIOLATION, cfg)
    want = reference.explore(len(procs), max_money)
    assert not want["ok"] and want["invariant"] == "NoMoneyCreated"
    for got in (res, lvl):
        assert not got.ok and not got.truncated
        assert (got.violation.kind, got.violation.name) == \
            ("invariant", "NoMoneyCreated")
        assert (got.generated, got.distinct, got.diameter) == \
            (want["generated"], want["distinct"], want["diameter"])
    assert len(res.violation.trace) == len(lvl.violation.trace) \
        == want["diameter"] + 1
    states, labels = _plain(res.violation.trace)
    ok, why = reference.check_trace(states, labels, len(procs), max_money,
                                    "NoMoneyCreated",
                                    min_len=want["diameter"] + 1)
    assert ok, why
    assert not any("no counterexample" in w for w in res.warnings)


def test_hand_counts_at_2x3(reference):
    """ISSUE 44's own numbers, from the other reference's step relation."""
    want = reference.explore(2, 3)
    assert (want["generated"], want["distinct"], want["diameter"]) == \
        (247, 166, 6)
    assert want["which"] == 1 and want["violating"] == 6
    solvent = reference.explore(2, 3, ("AliceBounded", "AliceSolvent"))
    assert solvent["invariant"] == "AliceSolvent"
    assert solvent["diameter"] == 4
    assert reference.explore(2, 3, ("AliceBounded",))["ok"]


@pytest.mark.parametrize("how", ["step-swapped", "last-replaced",
                                 "label-swapped", "too-long", "cut-short"])
def test_the_judge_refuses_a_corrupted_behaviour(tmp_path, reference, how):
    res, _ = _check(VIOLATION, _cfg(tmp_path, ("p1", "p2"), 3),
                    resident=True)
    states, labels = _plain(res.violation.trace)
    assert reference.check_trace(states, labels, 2, 3, "NoMoneyCreated",
                                 min_len=7)[0]
    if how == "step-swapped":
        states[2], states[3] = states[3], states[2]
    elif how == "last-replaced":
        states[-1] = dict(states[-1], bob=states[-1]["bob"] - 1)
    elif how == "label-swapped":
        labels[1], labels[2] = labels[2], labels[1]
        assert labels[1] != labels[2]
    elif how == "too-long":
        states.insert(1, states[0])
        labels.insert(1, "Terminating")
    else:
        states, labels = states[:-1], labels[:-1]
    ok, why = reference.check_trace(states, labels, 2, 3, "NoMoneyCreated",
                                    min_len=7)
    assert not ok, why


# ----------------------------------------------- deadlock and assert

def test_a_deadlock_returns_its_trace():
    cfg = os.path.join(SPECS, "portoy.cfg")
    spec = os.path.join(SPECS, "portoy.tla")
    res, sess = _check(spec, cfg, resident=True)
    lvl, _ = _check(spec, cfg)
    assert not res.ok and res.violation.kind == "deadlock"
    assert (res.generated, res.distinct, res.diameter) == \
        (lvl.generated, lvl.distinct, lvl.diameter)
    assert len(res.violation.trace) == len(lvl.violation.trace) > 1
    _replays(sess.model, res.violation.trace)
    # the last state has no successor at all
    from jaxmc.sem.enumerate import enumerate_next
    model = sess.model
    assert not list(enumerate_next(model.next, model.ctx(), model.vars,
                                   res.violation.trace[-1][0]))


def test_an_assert_returns_its_trace():
    """`specs/pcal_intro_buggy.tla`, whose verdict `jaxmc/corpus.py` pins:
    TLC's run of the README's race ends in this assertion."""
    spec = os.path.join(SPECS, "pcal_intro_buggy.tla")
    res, sess = _check(spec, None, resident=True)
    lvl, _ = _check(spec, None)
    assert not res.ok and res.violation.kind == "assert"
    # (an assert ends the resident loop at the chunk that holds it, the
    # level engine at the level's end: the counts differ, as before)
    assert len(res.violation.trace) == len(lvl.violation.trace) == 6
    assert res.diameter == lvl.diameter
    _replays(sess.model, res.violation.trace)
    assert res.violation.trace[-1][0]["alice_account"] < 0


# ------------------------------------- no_trace: the untraced program

def _first_dispatch(ex):
    """(lowered text, operands, key) of the resident program the engine
    would dispatch first, WITHOUT running it."""
    seen = {}
    get = ex._get_resident_run

    class _Stop(Exception):
        pass

    def grab(*key):
        fn = get(*key)

        def stop(*args):
            seen["text"] = fn.__wrapped__.lower(*args).as_text()
            seen["operands"] = len(args)
            seen["key"] = key
            raise _Stop
        return stop
    ex._get_resident_run = grab
    with pytest.raises(_Stop):
        ex.run()
    ex._get_resident_run = get
    return seen


def test_no_trace_is_the_untraced_program(tmp_path):
    from jaxmc.backend.bfs import TpuExplorer
    cfg = _cfg(tmp_path, ("p1", "p2"), 3)
    tel = obs.Telemetry(meta={})
    with obs.use(tel):
        quiet = CheckSession(SessionConfig(
            spec=VIOLATION, cfg=cfg, backend="jax", platform="cpu",
            chunk=64, resident=True, no_trace=True, res_caps=dict(CAPS)),
            tel=tel).compile()
        bare = TpuExplorer(quiet.model, resident=True, store_trace=False,
                           chunk=64, res_caps=dict(CAPS))
        kept = CheckSession(SessionConfig(
            spec=VIOLATION, cfg=cfg, backend="jax", platform="cpu",
            chunk=64, resident=True, res_caps=dict(CAPS, LogCap=512)),
            tel=tel).compile()
        assert quiet.engine._program_sig() == bare._program_sig()
        assert kept.engine._program_sig() != bare._program_sig()
        q, k, b = (_first_dispatch(ex)
                   for ex in (quiet.engine, kept.engine, bare))
    # ten operands, the parent's key, and the very text of an engine
    # built with store_trace False; a pinned LogCap changes nothing
    assert q["operands"] == 10 and len(q["key"]) == 5
    assert q["text"] == b["text"]
    assert "jaxmc.trace" not in q["text"]
    # the traced program: the log and its row count beside them
    assert k["operands"] == 12 and k["key"][5:] == (512, 64)
    log_shape = f"tensor<{512 + CAPS['FCap']}x{quiet.engine.PW}xi32>"
    assert log_shape in k["text"] and log_shape not in q["text"]


def test_no_trace_emits_none_of_the_names(tmp_path):
    cfg = _cfg(tmp_path, ("p1", "p2"), 3)
    for opts, want in ((dict(no_trace=True), False), ({}, True)):
        tel = obs.Telemetry(meta={})
        res, sess = _check(VIOLATION, cfg, tel=tel, resident=True, **opts)
        snap = tel.metrics_snapshot()
        phases = {p["name"] for p in tel.phase_list()}
        sites = set(tel.prof.sites)
        for name in TRACE_NAMES:
            assert (name in snap["counters"]) is want, name
        for name in ("search.trace", "trace.walk", "trace.decode"):
            assert (name in phases) is want, name
        assert ("bfs.trace_walk" in sites) is want
        assert len(res.violation.trace) == (7 if want else 1)
        if want:
            c = snap["counters"]
            assert c["search.trace_len"] == 7
            # levels 0..5 are logged, the level that ends the search is not
            assert c["search.log_rows"] == 9 + 18 + 27 + 48 + 37 + 18
            assert c["search.log_bytes"] == \
                4 * sess.engine.PW * c["search.log_rows"]
            assert 0 < c["search.trace_rows_expanded"] <= c["search.log_rows"]
        else:
            assert "no trace: --no-trace" in res.violation.trace[0][1]


# ---------------------------------------------------- the log's capacity

def test_a_log_too_small_grows_by_name(tmp_path, reference):
    """3 x 4: levels 0..5 hold more rows than the pinned LogCap; the engine
    says which capacity it grew, redoes the level, and the trace is whole
    (never a short one, never a clamped write into logged rows)."""
    cfg = _cfg(tmp_path, ("p1", "p2", "p3"), 4)
    want = reference.explore(3, 4)
    rows = want["levels"][0][0] + sum(new for _, _, new in
                                      want["levels"][:-1])
    assert rows > 256
    lines = []
    tel = obs.Telemetry(meta={})
    with obs.use(tel):
        sess = CheckSession(SessionConfig(
            spec=VIOLATION, cfg=cfg, backend="jax", platform="cpu",
            chunk=64, resident=True, res_caps=dict(CAPS, LogCap=256)),
            tel=tel, log=lines.append)
        res = sess.explore()
    grown = [ln for ln in lines if "growing LogCap" in ln]
    assert grown and "redone" in grown[0]
    assert sess.engine._res_caps["LogCap"] >= rows
    assert (res.generated, res.distinct, res.diameter) == \
        (want["generated"], want["distinct"], want["diameter"])
    states, labels = _plain(res.violation.trace)
    ok, why = reference.check_trace(states, labels, 3, 4, "NoMoneyCreated",
                                    min_len=want["diameter"] + 1)
    assert ok, why
    assert tel.metrics_snapshot()["counters"]["search.log_rows"] == rows


def test_the_engine_climbs_the_ladder_with_the_log(tmp_path, reference):
    """4 procs / MaxMoney 3 from small capacities, traces kept: the engine
    compiles the programs `tests/test_bench_pins.py::_cold_ladder` lists —
    the arithmetic behind `bench/pins/transfer_violation_4p.json` — in
    that order, the log's growths among them, and ends at the last."""
    from tests.test_bench_pins import _cold_ladder
    want = reference.explore(4, 3)
    start = {"SC": 1 << 12, "FCap": 1 << 9, "AccCap": 1 << 10, "VC": 256,
             "LogCap": 1 << 9}
    ladder = _cold_ladder(want["levels"], want["levels"][0][0], start)
    assert len({c["LogCap"] for c in ladder}) > 1  # the log grows too
    tel = obs.Telemetry(meta={})
    with obs.use(tel):
        sess = CheckSession(SessionConfig(
            spec=VIOLATION, cfg=_cfg(tmp_path, ("p1", "p2", "p3", "p4"), 3),
            backend="jax", platform="cpu", chunk=64, resident=True,
            res_caps=dict(start)), tel=tel)
        res = sess.explore()
    assert (res.generated, res.distinct, res.diameter) == \
        (want["generated"], want["distinct"], want["diameter"])
    assert len(res.violation.trace) == want["diameter"] + 1
    eng = sess.engine
    assert [dict(zip(("SC", "FCap", "AccCap", "VC", "CH", "LogCap"), key),
                 CH=None) for key in eng._res_cache] == \
        [dict(c, CH=None) for c in ladder]
    assert eng._res_caps == ladder[-1]


def test_several_dispatches_keep_one_log(tmp_path, reference):
    """One level a dispatch: the log and its offsets cross dispatches."""
    cfg = _cfg(tmp_path, ("p1", "p2"), 3)
    tel = obs.Telemetry(meta={})
    with obs.use(tel):
        sess = CheckSession(SessionConfig(
            spec=VIOLATION, cfg=cfg, backend="jax", platform="cpu",
            chunk=64, resident=True, res_caps=dict(CAPS, LogCap=256)),
            tel=tel).compile()
        sess.engine._res_maxlvl = 1
        res = sess.explore()
    assert tel.prof.sites["bfs.resident_run"].as_dict()["dispatches"] >= 6
    states, labels = _plain(res.violation.trace)
    ok, why = reference.check_trace(states, labels, 2, 3, "NoMoneyCreated",
                                    min_len=7)
    assert ok, why


# ------------------------------------ options that could take a trace

def _extending(tmp_path, base, name, body, cfg_text):
    """A module in tmp_path that EXTENDS specs/<base>.tla and adds an
    invariant that fails, and its cfg."""
    shutil.copy(os.path.join(SPECS, base + ".tla"), tmp_path)
    (tmp_path / (name + ".tla")).write_text(
        f"---- MODULE {name} ----\nEXTENDS {base}\n{body}\n====\n")
    (tmp_path / (name + ".cfg")).write_text(cfg_text)
    return str(tmp_path / (name + ".tla")), str(tmp_path / (name + ".cfg"))


def _same_verdict_and_replays(spec, cfg, kind="invariant", **opts):
    res, sess = _check(spec, cfg, resident=True, **opts)
    lvl, _ = _check(spec, cfg, **opts)
    assert not res.ok and res.violation.kind == lvl.violation.kind == kind
    assert res.violation.name == lvl.violation.name
    assert (res.generated, res.distinct, res.diameter) == \
        (lvl.generated, lvl.distinct, lvl.diameter)
    assert len(res.violation.trace) == len(lvl.violation.trace) > 1
    _replays(sess.model, res.violation.trace)
    assert not any("no counterexample" in w for w in res.warnings)
    return res, sess


def test_symmetry_keeps_the_trace(tmp_path):
    spec, cfg = _extending(
        tmp_path, "symtoy", "symviol",
        "NoSecondTurn == \\A p \\in P : turns[p] < 2",
        "SPECIFICATION Spec\nCONSTANTS\n  P = {p1, p2, p3}\n"
        "  None = None\nSYMMETRY Perms\nINVARIANT NoSecondTurn\n")
    res, sess = _same_verdict_and_replays(spec, cfg)
    assert sess.engine.canon_fn is not None  # the reduction really ran
    turns = res.violation.trace[-1][0]["turns"]
    assert max(turns.d.values()) == 2


def test_a_view_keeps_the_trace(tmp_path):
    spec, cfg = _extending(
        tmp_path, "viewtoy", "viewviol", "Small == x < 3",
        "SPECIFICATION Spec\nVIEW V\nINVARIANT Small\n")
    res, sess = _same_verdict_and_replays(spec, cfg)
    assert sess.engine.view_fn is not None
    assert res.violation.trace[-1][0]["x"] == 3


def test_por_keeps_the_trace():
    spec = os.path.join(SPECS, "portoy.tla")
    cfg = os.path.join(SPECS, "portoy_bad.cfg")
    res, sess = _same_verdict_and_replays(spec, cfg, por=True)
    assert sess.engine._por_stats["masked"] > 0  # the mask really ran
    assert res.violation.trace[-1][0]["flag"] is True


def test_a_seen_cap_keeps_the_trace(tmp_path):
    """`--seen-cap`: the run spills before it reaches the violation; the
    host drops cold duplicates from a frontier the log already holds,
    which leaves the log a superset and every logged row reachable."""
    spec = os.path.join(SPECS, "ooc_scaled.tla")
    cfg = os.path.join(SPECS, "ooc_scaled_bad.cfg")
    res, sess = _check(spec, cfg, resident=True, chunk=64, seen_cap=128,
                       seen_spill=str(tmp_path / "spill"))
    lvl, _ = _check(spec, cfg)
    assert res.tiers and res.tiers["spills"] > 0
    assert not res.ok and (res.violation.kind, res.violation.name) == \
        (lvl.violation.kind, lvl.violation.name) == ("invariant", "NoMeet")
    assert (res.generated, res.distinct, res.diameter) == \
        (lvl.generated, lvl.distinct, lvl.diameter)
    assert len(res.violation.trace) == len(lvl.violation.trace) > 1
    _replays(sess.model, res.violation.trace)


def test_resume_gives_the_trace_up_by_name(tmp_path):
    cfg = _cfg(tmp_path, ("p1", "p2"), 3)
    ck = str(tmp_path / "v.ck")
    part, _ = _check(VIOLATION, cfg, resident=True, max_states=40,
                     checkpoint=ck)
    assert part.truncated and os.path.exists(ck)
    res, _ = _check(VIOLATION, cfg, resident=True, resume=ck)
    assert not res.ok and res.violation.name == "NoMoneyCreated"
    assert (res.generated, res.distinct) == (247, 166)
    assert len(res.violation.trace) == 1
    assert "no trace: --resume" in res.violation.trace[0][1]
    named = [w for w in res.warnings if "no counterexample trace" in w]
    assert named and "--resume" in named[0]


def test_the_walk_takes_the_lowest_slot_then_action(tmp_path):
    """Deterministic: two searches give the same behaviour, word for
    word, and a walk in smaller chunks the same again."""
    cfg = _cfg(tmp_path, ("p1", "p2", "p3"), 3)
    a, sess = _check(VIOLATION, cfg, resident=True)
    b = sess.explore()
    assert a.violation.trace == b.violation.trace
    c, _ = _check(VIOLATION, cfg, resident=True, chunk=128)
    assert a.violation.trace == c.violation.trace
