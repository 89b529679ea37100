"""The resident engine with its key sort on a ladder (ISSUE 42).

A resident level's candidates are a prefix of its accumulator, and
`bfs._rank_merge` sorts the smallest rung of `bfs._sort_rungs(AccCap)`
that holds them (tests/test_rank_merge.py has the kernel's contract).
Here the engine at toy size with the floor lowered, so that a search
runs on three rungs or more: its counts, verdict and trace are the
interpreter's — plain, under POR (invalid rows INSIDE the prefix), with
a level rolled back and run again, and `search.slots_sorted` is the sum
over the levels the dispatches really ran of the rung the ONE rule
(`bfs._sort_rung_index`) gives for each level's candidates.  The capped
engine's spills are in tests/test_bench_pins.py, beside their
arithmetic."""

import os

import pytest

pytest.importorskip("jax")

from jaxmc import obs  # noqa: E402
from jaxmc.backend import bfs  # noqa: E402
from jaxmc.engine.explore import format_trace  # noqa: E402
from jaxmc.session import CheckSession, SessionConfig  # noqa: E402

from test_bench_pins import TRANSFER, _reference, _toy_cfg  # noqa: E402

SPECS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "specs")
RESIDENT = dict(backend="jax", platform="cpu", resident=True)
RUNG_MIN = 64


@pytest.fixture(autouse=True)
def _toy_ladder(monkeypatch):
    monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")
    monkeypatch.setattr(bfs, "_SORT_RUNG_MIN", RUNG_MIN)


def _answer(res):
    return (res.ok, res.generated, res.distinct, res.diameter,
            None if res.violation is None
            else (res.violation.kind, format_trace(res.violation)))


def _levels_run(tel):
    """The level every dispatch's every turn ran, from the dispatch
    records: the depths a dispatch passed, and the level it rolled back
    where it ended in a growth status (depth stays)."""
    grow = (bfs.ST_OVF_SEEN, bfs.ST_OVF_FRONT, bfs.ST_OVF_ACC,
            bfs.ST_OVF_VC)
    ran, depth = [], 0
    for rec in tel.levels:
        assert rec["dispatch"]
        ran += list(range(depth, rec["level"]))
        if rec["status"] in grow:
            ran.append(rec["level"])
        depth = rec["level"]
    return ran


def _rung(cand, acc_cap):
    return bfs._sort_rungs(acc_cap)[bfs._sort_rung_index(cand, acc_cap)]


@pytest.mark.parametrize("case", ["plain", "seen_overflow_redo"])
def test_resident_counts_and_slots_sorted_on_the_ladder(case, tmp_path):
    """4 procs / MaxMoney 2 (19,101 generated, 13 levels of 16 to 3,508
    candidates): the plain reference's and the interpreter's counts, and
    the sorted slots by the rule — also where a seen table that starts
    too small rolls levels back, grows and runs them again (they sorted
    their rung both times)."""
    want = _reference().explore(4, 2)
    levels = want["levels"]
    caps = {"SC": 1 << 14, "FCap": 1 << 11, "AccCap": 1 << 13, "VC": 256}
    if case == "seen_overflow_redo":
        caps["SC"] = 1 << 9
    cfg = _toy_cfg(tmp_path, 4, 2)
    interp = CheckSession(SessionConfig(
        spec=TRANSFER, cfg=cfg, backend="interp")).explore()
    tel = obs.Telemetry()
    with obs.use(tel):
        sess = CheckSession(SessionConfig(
            spec=TRANSFER, cfg=cfg, no_trace=True, res_caps=dict(caps),
            chunk=64, **RESIDENT), tel=tel)
        sess.compile()
        sess.engine._res_maxlvl = 3
        res = sess.explore()
    assert _answer(res) == _answer(interp) == \
        (True, want["generated"], want["distinct"], want["diameter"], None)
    ran = _levels_run(tel)
    redone = len(ran) - len(levels)
    assert sorted(set(ran)) == list(range(len(levels)))
    end = sess.engine._res_caps
    if case == "plain":
        assert redone == 0 and end == caps
    else:
        # only the seen table grew: every level's candidates were all in
        # the accumulator when its merge ran, the rolled-back ones' too
        assert redone >= 2 and end["SC"] > caps["SC"]
        assert {k: end[k] for k in ("FCap", "AccCap", "VC")} == \
            {k: caps[k] for k in ("FCap", "AccCap", "VC")}
    sorted_on = [_rung(levels[lv][1], caps["AccCap"]) for lv in ran]
    assert len(set(sorted_on)) >= 3 and max(sorted_on) < caps["AccCap"]
    c = tel.counters
    assert c["search.slots_sorted"] == sum(sorted_on)
    assert c["search.slots_sorted"] < len(ran) * caps["AccCap"]
    assert c["search.rows_valid"] == want["generated"] - levels[0][0]


@pytest.mark.parametrize("por", [False, True], ids=["plain", "por"])
@pytest.mark.parametrize("cfg", ["portoy_bad", "portoy"])
def test_resident_verdict_and_trace_with_and_without_the_ladder(
        cfg, por, monkeypatch):
    """portoy's invariant violation and its deadlock, with --por (masked
    candidates are invalid rows inside the sorted prefix) and without:
    the interpreter's verdict, and the counts, the depth and the trace
    (the resident engine's is the violating state) that the same engine
    answers with ONE rung, the whole accumulator sorted a level."""
    paths = dict(spec=os.path.join(SPECS, "portoy.tla"),
                 cfg=os.path.join(SPECS, cfg + ".cfg"), por=por)
    caps = {"SC": 1 << 10, "FCap": 256, "AccCap": 1 << 10, "VC": 128}
    interp = CheckSession(SessionConfig(backend="interp", **paths)) \
        .explore()

    def resident():
        tel = obs.Telemetry()
        with obs.use(tel):
            res = CheckSession(SessionConfig(
                res_caps=dict(caps), chunk=64, **RESIDENT, **paths),
                tel=tel).explore()
        lvls = tel.counters["search.seen_slots"] // caps["SC"]
        return res, tel, lvls

    res, tel, lvls = resident()
    assert bfs._sort_rungs(caps["AccCap"])[-1] == RUNG_MIN
    assert lvls * RUNG_MIN <= tel.counters["search.slots_sorted"] \
        < lvls * caps["AccCap"]
    assert not res.ok and res.violation.kind == interp.violation.kind \
        == ("invariant" if cfg == "portoy_bad" else "deadlock")
    if not por:
        # ... reached where the interpreter's shortest trace ends
        assert res.violation.trace[-1][0] in \
            [state for state, _ in interp.violation.trace]
    else:
        assert tel.gauges.get("por.engine") == "device"
        assert tel.gauges.get("por.device_masked_arms", 0) > 0
    monkeypatch.setattr(bfs, "_SORT_RUNG_MIN", caps["AccCap"])
    whole, tel1, lvls1 = resident()
    assert _answer(res) == _answer(whole) and lvls == lvls1
    assert tel1.counters["search.slots_sorted"] == lvls * caps["AccCap"]
    for name in ("search.rows_valid", "search.rows_new",
                 "search.slots_probed", "search.slots_merged"):
        assert tel.counters[name] == tel1.counters[name], name
