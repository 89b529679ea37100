r"""The benchmark's resident pins as arithmetic on their own levels, and the
resident engine in the deep cell's proportions (ISSUE 30).

`bench/pins/*.json` pin, for every resident cell, the answer, the plain
reference's levels and the capacities (`res_caps`) the cell's program is
compiled at.  The rule that pinned them: what one cold run at the accelerator
defaults LEAVES, each capacity a step of its own x4 ladder.  `_cold_ladder`
below is that run as arithmetic on the levels, in the engine's order of
overflows; `test_the_engine_climbs_the_ladder_the_arithmetic_gives` holds the
engine itself to it (ISSUE 30's repair: a frontier growth used to lift AccCap
onto FCap's ladder, and the real rung's cold run ended at AccCap 2^24, not the
pinned 2^23).  `desk-deep-4p` runs SC 2^24 = 512 merge blocks and AccCap 2^23
= 64 query blocks; the last test runs those COUNTS of blocks at a size XLA:CPU
answers in seconds, over several dispatches.
"""

import glob
import importlib.util
import json
import os

import pytest

from jaxmc import obs
from jaxmc.session import CheckSession, SessionConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRANSFER = os.path.join(REPO, "bench", "specs", "transfer_scaled.tla")
#: the resident engine's accelerator defaults (`bfs._run_resident`)
DEFAULTS = {"SC": 1 << 20, "FCap": 1 << 16, "AccCap": 1 << 17, "VC": 1 << 14}


def _pins(name):
    with open(os.path.join(REPO, "bench", "pins", name + ".json")) as fh:
        return json.load(fh)


RESIDENT_PINS = sorted(
    name for name in (os.path.basename(p)[:-len(".json")] for p in glob.glob(
        os.path.join(REPO, "bench", "pins", "*.json")))
    if "FCap" in _pins(name).get("res_caps", {}))


@pytest.fixture(autouse=True)
def _no_capacity_profiles(monkeypatch):
    monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")


def _needs(levels, initial, vc):
    """What a whole search asks of each capacity, from the reference's
    levels [frontier, candidates, new]: the three inequalities."""
    seen, most = initial, 0
    for _, cand, new in levels:
        most = max(most, seen + cand)  # every candidate could be new
        seen += new
    return {"SC": most,
            "FCap": max(max(f, new) for f, _, new in levels),
            "AccCap": max(cand for _, cand, _ in levels) + vc}


def _ladder_step(default, need):
    cap = default
    while cap < need:
        cap *= 4
    return cap


def _cold_ladder(levels, initial, defaults):
    """The capacities of every program a cold resident run compiles, in
    order, from the reference's levels: a level is redone after each x4
    growth; the level's status names the accumulator first, then the seen
    table, then the frontier (`bfs._get_resident_run`'s `level`), and
    AccCap keeps its invariants by steps of its own ladder."""
    caps = dict(defaults)
    programs, seen = [dict(caps)], initial
    for _, cand, new in levels:
        while True:
            if cand + caps["VC"] > caps["AccCap"]:
                what = "AccCap"
            elif seen + cand > caps["SC"]:
                what = "SC"
            elif new > caps["FCap"]:
                what = "FCap"
            else:
                break
            caps[what] *= 4
            caps["AccCap"] = _ladder_step(
                caps["AccCap"], max(2 * caps["VC"], caps["FCap"]))
            programs.append(dict(caps))
        seen += new
    return programs


def test_there_are_resident_pins():
    assert {"transfer_scaled", "transfer_scaled_4p8",
            "transfer_scaled_4p"} <= set(RESIDENT_PINS)


@pytest.mark.parametrize("name", RESIDENT_PINS)
def test_res_caps_are_the_ladder_steps_that_hold_the_levels(name):
    pins = _pins(name)
    caps, levels = pins["res_caps"], pins["levels"]
    assert sum(c for _, c, _ in levels) + levels[0][0] == pins["generated"]
    initial = pins["distinct"] - sum(new for _, _, new in levels)
    assert initial == levels[0][0]
    assert caps["VC"] == DEFAULTS["VC"]
    need = _needs(levels, initial, caps["VC"])
    for key in ("SC", "FCap", "AccCap"):
        assert need[key] <= caps[key], (key, need[key])
        # a step of the x4 ladder from the default, and the smallest
        assert caps[key] == _ladder_step(DEFAULTS[key], need[key]), key
    # the engine's own invariants (`_run_resident`)
    assert caps["AccCap"] >= max(2 * caps["VC"], caps["FCap"])
    # and what one cold run from the defaults leaves
    assert _cold_ladder(levels, initial, DEFAULTS)[-1] == caps


def test_the_real_rungs_cold_ladder():
    """The 4-process rung: seven programs from the defaults to the pins
    (my chip run, PR 30, before the repair: nine, ending at AccCap 2^24;
    its first growth, FCap to 2^18, is the one that lifts AccCap)."""
    pins = _pins("transfer_scaled_4p")
    ladder = _cold_ladder(pins["levels"], pins["levels"][0][0], DEFAULTS)
    assert len(ladder) == 7
    assert ladder[1] == dict(DEFAULTS, FCap=1 << 18, AccCap=1 << 19)
    assert ladder[-1] == pins["res_caps"]


def _reference():
    spec = importlib.util.spec_from_file_location(
        "plain_reference", os.path.join(REPO, "bench", "reference",
                                        "transfer_scaled.py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    return reference


def _toy_cfg(tmp_path, procs, max_money):
    cfg = tmp_path / "t.cfg"
    cfg.write_text(
        "SPECIFICATION Spec\nINVARIANT AliceBounded\nCONSTANTS\n"
        "  Procs = {%s}\n  MaxMoney = %d\n"
        % (", ".join("p%d" % (i + 1) for i in range(procs)), max_money))
    return str(cfg)


def test_the_engine_climbs_the_ladder_the_arithmetic_gives(tmp_path):
    """The normal path with no pins: 4 procs / MaxMoney 3 (13 levels, as
    the real rung) from capacities in the accelerator defaults' shape
    (AccCap = 2 x FCap, so a frontier growth passes it).  The engine
    compiles the programs `_cold_ladder` lists, in that order, ends at
    the last, and the answer is the reference's.  Under the bare max()
    the fifth program had AccCap 2^13 and the run ended at 2^15: which
    rule ends lower is the model's luck; this one is arithmetic."""
    pytest.importorskip("jax")
    want = _reference().explore(4, 3)
    start = {"SC": 1 << 12, "FCap": 1 << 9, "AccCap": 1 << 10, "VC": 256}
    ladder = _cold_ladder(want["levels"], want["levels"][0][0], start)
    assert len(ladder) == 7
    assert (ladder[3]["FCap"], ladder[3]["AccCap"]) == (1 << 11, 1 << 12)
    assert (ladder[4]["FCap"], ladder[4]["AccCap"]) == (1 << 13, 1 << 14)
    tel = obs.Telemetry()
    with obs.use(tel):
        sess = CheckSession(SessionConfig(
            spec=TRANSFER, cfg=_toy_cfg(tmp_path, 4, 3), backend="jax",
            platform="cpu", resident=True, no_trace=True, res_caps=start,
            chunk=64), tel=tel)
        res = sess.explore()
    assert (res.generated, res.distinct, res.diameter, res.ok) == \
        (want["generated"], want["distinct"], want["diameter"], True)
    eng = sess.engine
    assert [dict(zip(("SC", "FCap", "AccCap", "VC"), key))
            for key in eng._res_cache] == ladder
    assert eng._res_caps == ladder[-1]


def test_the_deep_pins_are_the_corpus_manifests():
    """`jaxmc/corpus.py` seeds kernelbench and chip_smoke.py from the same
    capacities (its AccCap 2^22 could not hold the widest level)."""
    from jaxmc.corpus import CASES
    pins = _pins("transfer_scaled_4p")
    case, = [c for c in CASES if c.cfg == "specs/transfer_scaled_4p.cfg"]
    assert (case.generated, case.distinct) == (pins["generated"],
                                               pins["distinct"])
    assert {k: case.res_caps[k] for k in pins["res_caps"]} == \
        pins["res_caps"]


def test_resident_engine_in_the_deep_proportions(tmp_path, monkeypatch):
    """3 procs / MaxMoney 4 against the plain reference, the capacities in
    the deep pins' proportions (SC : AccCap : FCap = 4 : 2 : 1, the table
    512 merge blocks, the keys 64 query blocks) and the search split over
    several dispatches: counts, and the two block counters equal to what
    the reference's levels give by the kernels' own rules."""
    pytest.importorskip("jax")
    from jaxmc.backend import bfs
    want = _reference().explore(3, 4)
    levels = want["levels"]
    caps = {"SC": 1 << 13, "FCap": 1 << 11, "AccCap": 1 << 12, "VC": 256}
    need = _needs(levels, levels[0][0], caps["VC"])
    assert all(need[k] <= caps[k] for k in need)
    deep = _pins("transfer_scaled_4p")["res_caps"]
    assert (deep["SC"] // bfs._merge_block_rows(deep["SC"]),
            deep["AccCap"] // bfs._probe_block_rows(deep["AccCap"]),
            deep["SC"] // deep["AccCap"], deep["AccCap"] // deep["FCap"]) \
        == (512, 64, 2, 2)
    monkeypatch.setattr(bfs, "_MERGE_BLOCK_ROWS", caps["SC"] // 512)
    monkeypatch.setattr(bfs, "_PROBE_BLOCK_MIN", 4)
    B, QB = bfs._merge_block_rows(caps["SC"]), \
        bfs._probe_block_rows(caps["AccCap"])
    assert (caps["SC"] // B, caps["AccCap"] // QB,
            caps["SC"] // caps["AccCap"], caps["AccCap"] // caps["FCap"]) \
        == (512, 64, 2, 2)
    tel = obs.Telemetry()
    with obs.use(tel):
        sess = CheckSession(SessionConfig(
            spec=TRANSFER, cfg=_toy_cfg(tmp_path, 3, 4), backend="jax",
            platform="cpu",
            resident=True, no_trace=True, res_caps=caps, chunk=64), tel=tel)
        sess.compile()
        sess.engine._res_maxlvl = 2
        res = sess.explore()
    assert (res.generated, res.distinct, res.diameter, res.ok) == \
        (want["generated"], want["distinct"], want["diameter"], True)
    assert sess.engine._res_caps == caps  # nothing regrew
    assert tel.prof.sites["bfs.resident_run"].dispatches == \
        -(-len(levels) // 2) > 1
    c = tel.counters
    seen2 = [levels[0][0]]
    for _, _, new in levels:
        seen2.append(seen2[-1] + new)
    assert c["search.slots_merged"] == sum(-(-n // B) * B
                                           for n in seen2[1:])
    assert c["search.slots_probed"] == sum(-(-cand // QB) * QB
                                           for _, cand, _ in levels)
    assert c["search.seen_slots"] == len(levels) * caps["SC"]
    assert c["search.slots_sorted"] == len(levels) * caps["AccCap"]
