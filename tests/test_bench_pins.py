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
= 64 query blocks; `test_resident_engine_in_the_deep_proportions` runs those
COUNTS of blocks at a size XLA:CPU answers in seconds, over several dispatches.

`desk-ooc-4p8` (ISSUE 32) pins a device CAP with its capacities: there SC is
the cap, a seen-table overflow spills instead of growing, and what a search
does — which levels spill, how many keys go cold, how many candidates are
generated twice, how many keys the host probes and drops — is `_cold_spills`,
the plain reference's successor function under the engine's spill rule.  The
pins' `tier` block is that arithmetic at the cell's size, and the last test
holds the resident engine's `tier.*` counters to it at toy size.
"""

import glob
import importlib.util
import json
import os

import numpy as np
import pytest

from jaxmc import obs
from jaxmc.session import CheckSession, SessionConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRANSFER = os.path.join(REPO, "bench", "specs", "transfer_scaled.tla")
#: the resident engine's accelerator defaults (`bfs._run_resident`)
DEFAULTS = {"SC": 1 << 20, "FCap": 1 << 16, "AccCap": 1 << 17, "VC": 1 << 14}
#: ... and where the state log of a search that keeps traces starts (PR 44)
LOG_DEFAULT = 1 << 20


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


def _entered(level):
    """The rows a level puts into the seen table: its new rows, or — in a
    fourth column, under a CONSTRAINT (PR 51) — the rows fingerprinted,
    kept and discarded alike."""
    return level[3] if len(level) > 3 else level[2]


def _needs(levels, initial, vc, cap=None):
    """What a whole search asks of each capacity, from the reference's
    levels [frontier, candidates, new(, entered the table)]: the three
    inequalities.  Under a device cap the table spills before it grows,
    so it need only seat the widest level's candidates beside nothing."""
    seen, most = initial, 0
    for level in levels:
        most = max(most, seen + level[1])  # every candidate could be new
        seen += _entered(level)
    if cap is not None:
        most = max(level[1] for level in levels)
    return {"SC": most,
            "FCap": max(max(level[0], level[2]) for level in levels),
            "AccCap": max(level[1] for level in levels) + vc}


def _ladder_step(default, need):
    cap = default
    while cap < need:
        cap *= 4
    return cap


def _cold_ladder(levels, initial, defaults, cap=None):
    """The capacities of every program a cold resident run compiles, in
    order, from the reference's levels: a level is redone after each x4
    growth; the level's status names the accumulator first, then the seen
    table, then the frontier (`bfs._get_resident_run`'s `level`), and
    AccCap keeps its invariants by steps of its own ladder.  Under a device
    `cap` a full table at the cap spills (and the level is redone against
    an empty one) instead of growing."""
    caps = dict(defaults)
    programs, seen, logged = [dict(caps)], initial, initial
    for lvl, level in enumerate(levels):
        cand, new = level[1], level[2]
        # with "LogCap" among the defaults the search keeps its state log
        # and ENDS at the last level given (a violation, PR 44): every
        # level it goes on from is appended, after the three checks above
        goes_on = "LogCap" in caps and lvl < len(levels) - 1
        while True:
            if cand + caps["VC"] > caps["AccCap"]:
                what = "AccCap"
            elif seen + cand > caps["SC"] and cap is not None \
                    and caps["SC"] >= cap and seen:
                seen = 0
                continue
            elif seen + cand > caps["SC"]:
                what = "SC"
            elif new > caps["FCap"]:
                what = "FCap"
            elif goes_on and logged + new > caps["LogCap"]:
                what = "LogCap"
            else:
                break
            caps[what] *= 4
            caps["AccCap"] = _ladder_step(
                caps["AccCap"], max(2 * caps["VC"], caps["FCap"]))
            programs.append(dict(caps))
        seen += _entered(level)
        logged += new if goes_on else 0
    return programs


def _reference():
    spec = importlib.util.spec_from_file_location(
        "plain_reference", os.path.join(REPO, "bench", "reference",
                                        "transfer_scaled.py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    return reference


def _cold_spills(procs, max_money, cap):
    """One search of the resident engine under a device cap of `cap` rows,
    as arithmetic on real key sets: the plain reference's own successor
    function (an int64 a state: exact keys) with the engine's rule.  A level
    whose candidates do not fit beside the hot keys (`seen_count + candidates
    > SC`) spills ALL hot keys as one cold run and runs again against an
    empty table; the table then admits every candidate it has not seen
    itself (a cold duplicate too: it sits in the table AND in a run from
    then on); once a run exists the host probes each level's device-new
    keys against the runs and drops the duplicates before they are counted
    or explored (`keys_verified`: a probed key once for every run it is in
    — what passes a run's fence where no two keys share their leading 8
    bytes and the runs stay apart).  The levels come back as [frontier,
    candidates, device-new, new]."""
    ref = _reference()
    codec = ref._Codec(procs, max_money)
    frontier = hot = np.unique(ref._init_states(codec))
    runs, out = [], {"spills": [], "redone_rows": 0, "keys_probed": 0,
                     "keys_dropped": 0, "keys_verified": 0, "levels": []}
    generated = distinct = int(frontier.size)
    depth = 0
    while True:
        succ = ref._successors(codec, frontier)
        generated += int(succ.size)
        if hot.size + succ.size > cap and hot.size:
            runs.append(hot)
            out["spills"].append([depth, int(hot.size)])
            out["redone_rows"] += int(succ.size)
            hot = np.empty(0, np.int64)
        assert hot.size + succ.size <= cap, "the cap would be breached"
        cand = np.unique(succ)
        device_new = new = cand[~np.isin(cand, hot, assume_unique=True)]
        hot = np.union1d(hot, device_new)
        if runs and device_new.size:
            dup = np.isin(device_new, np.concatenate(runs))
            out["keys_probed"] += int(device_new.size)
            out["keys_dropped"] += int(dup.sum())
            out["keys_verified"] += sum(
                int(np.isin(device_new, run, assume_unique=True).sum())
                for run in runs)
            new = device_new[~dup]
        out["levels"].append([int(frontier.size), int(succ.size),
                              int(device_new.size), int(new.size)])
        distinct += int(new.size)
        if not new.size:
            break
        frontier = new
        depth += 1
    cold = np.concatenate(runs) if runs else np.empty(0, np.int64)
    out.update(generated=generated, distinct=distinct, diameter=depth,
               spilled_keys=int(cold.size), cold_keys=int(cold.size),
               cold_distinct=int(np.unique(cold).size),
               hot_keys_at_end=int(hot.size))
    return out


def test_there_are_resident_pins():
    assert {"transfer_scaled", "transfer_scaled_4p8",
            "transfer_scaled_4p", "transfer_scaled_4p8_ooc",
            "transfer_violation_4p",
            "transfer_symmetry_5p",
            "transfer_retry_4p"} <= set(RESIDENT_PINS)


@pytest.mark.parametrize("name", RESIDENT_PINS)
def test_res_caps_are_the_ladder_steps_that_hold_the_levels(name):
    pins = _pins(name)
    caps, levels = dict(pins["res_caps"]), pins["levels"]
    log_cap = caps.pop("LogCap", None)
    if log_cap is not None:
        # a search that keeps its state log and ENDS in a violation (PR
        # 44): the log holds the initial frontier and every level the
        # search went on from — not the last, which holds the bad row —
        # and LogCap is the step of its own x4 ladder that seats them
        assert pins["verdict"] == "invariant"
        logged = levels[0][0] + sum(new for _, _, new in levels[:-1])
        assert logged == pins["logged_rows"]
        assert log_cap == _ladder_step(LOG_DEFAULT, logged)
        assert pins["trace_len"] == pins["diameter"] + 1 == len(levels) + 1
        # ... and what one cold run that keeps traces leaves, log and all
        assert _cold_ladder(levels, levels[0][0], dict(
            DEFAULTS, LogCap=LOG_DEFAULT))[-1] == pins["res_caps"]
    # under SYMMETRY every initial state is generated and the orbits are
    # the frontier (PR 47): the pins say how many were generated
    assert sum(c for _, c, _ in levels) + pins.get(
        "initial_generated", levels[0][0]) == pins["generated"]
    initial = pins["distinct"] - sum(new for _, _, new in levels)
    assert initial == levels[0][0]
    assert caps["VC"] == DEFAULTS["VC"]
    if "fingerprinted_levels" in pins:
        # under a CONSTRAINT (PR 51) the table holds the rows it
        # discarded too: SC follows the rows that ENTER it, FCap the rows
        # kept
        entered = pins["fingerprinted_levels"]
        assert len(entered) == len(levels)
        assert all(e >= new for e, (_, _, new) in zip(entered, levels))
        assert pins["fingerprinted"] == initial + sum(entered) == \
            pins["distinct"] + pins["discarded"]
        levels = [level + [e] for level, e in zip(levels, entered)]
    cap = pins.get("seen_cap")
    if cap is not None:
        # under a cap the table holds a level's DEVICE-new rows, its cold
        # duplicates too: the capacities follow those levels
        sim = _cold_spills(pins["procs"], pins["max_money"], cap)
        assert [[f, c, n] for f, c, _, n in sim["levels"]] == levels
        levels = [[f, c, dn] for f, c, dn, _ in sim["levels"]]
        # the cap IS the table, and the smallest power of two that is not
        # breached: it seats the widest level beside an empty table
        assert caps["SC"] == cap
        assert cap // 2 < max(c for _, c, _ in levels) <= cap
    need = _needs(levels, initial, caps["VC"], cap)
    for key in ("SC", "FCap", "AccCap"):
        assert need[key] <= caps[key], (key, need[key])
        # a step of the x4 ladder from the default, and the smallest
        assert caps[key] == _ladder_step(DEFAULTS[key], need[key]), key
    # the engine's own invariants (`_run_resident`)
    assert caps["AccCap"] >= max(2 * caps["VC"], caps["FCap"])
    # and what one cold run from the defaults leaves
    assert _cold_ladder(levels, initial, DEFAULTS, cap)[-1] == caps


def test_the_real_rungs_cold_ladder():
    """The 4-process rung: seven programs from the defaults to the pins
    (my chip run, PR 30, before the repair: nine, ending at AccCap 2^24;
    its first growth, FCap to 2^18, is the one that lifts AccCap)."""
    pins = _pins("transfer_scaled_4p")
    ladder = _cold_ladder(pins["levels"], pins["levels"][0][0], DEFAULTS)
    assert len(ladder) == 7
    assert ladder[1] == dict(DEFAULTS, FCap=1 << 18, AccCap=1 << 19)
    assert ladder[-1] == pins["res_caps"]
    # the same rung cut off at the violation, traces kept: one more
    # program, the log's one growth (PR 44)
    viol = _pins("transfer_violation_4p")
    ladder = _cold_ladder(viol["levels"], viol["levels"][0][0],
                          dict(DEFAULTS, LogCap=LOG_DEFAULT))
    assert len(ladder) == 8 and ladder[-1] == viol["res_caps"]
    assert [a["LogCap"] != b["LogCap"] for a, b in
            zip(ladder, ladder[1:])].count(True) == 1
    # five processes under SYMMETRY Perms (PR 47): the orbits are the
    # levels, 4,368 of the 248,832 initial states the first; eight
    # programs, and the frontier stays at 2^20 — its largest, 1,038,158,
    # is within 1 % of it — where the 4-process rung's needs 2^22
    sym = _pins("transfer_symmetry_5p")
    ladder = _cold_ladder(sym["levels"], sym["levels"][0][0], DEFAULTS)
    assert len(ladder) == 8 and ladder[-1] == sym["res_caps"]
    assert ladder[1] == dict(DEFAULTS, AccCap=1 << 19)
    assert [p["FCap"] for p in ladder][-1] == 1 << 20
    assert sym["initial_generated"] == sym["max_money"] ** sym["procs"]
    # four processes that retry, bounded by the cfg's CONSTRAINT alone
    # (PR 51): eight programs too, the same four capacities at the end;
    # the table is sized by the rows that enter it — by the kept rows
    # alone, 8,320,026, SC would stop at 2^24 as well, but seen +
    # candidates reads 12,930,041 where the kept rows give 8,320,257
    retry = _pins("transfer_retry_4p")
    levels = [level + [e] for level, e in zip(
        retry["levels"], retry["fingerprinted_levels"])]
    ladder = _cold_ladder(levels, levels[0][0], DEFAULTS)
    assert len(ladder) == 8 and ladder[-1] == retry["res_caps"] == \
        sym["res_caps"]
    assert [k for a, b in zip(ladder, ladder[1:]) for k in a
            if a[k] != b[k]] == ["AccCap", "FCap", "AccCap", "SC", "FCap",
                                 "AccCap", "SC"]
    assert _needs(levels, levels[0][0], DEFAULTS["VC"])["SC"] == 12930041


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
    """`jaxmc/corpus.py` seeds chip_smoke.py from the same
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
    512 merge blocks, the keys 64 query blocks and a sort ladder of nine
    rungs) and the search split over several dispatches: counts, and the
    two block counters and the sorted slots equal to what the reference's
    levels give by the kernels' own rules."""
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
            deep["AccCap"] // bfs._sort_rungs(deep["AccCap"])[-1],
            deep["SC"] // deep["AccCap"], deep["AccCap"] // deep["FCap"]) \
        == (512, 64, 256, 2, 2)
    monkeypatch.setattr(bfs, "_MERGE_BLOCK_ROWS", caps["SC"] // 512)
    monkeypatch.setattr(bfs, "_PROBE_BLOCK_MIN", 4)
    monkeypatch.setattr(bfs, "_SORT_RUNG_MIN", caps["AccCap"] // 256)
    B, QB = bfs._merge_block_rows(caps["SC"]), \
        bfs._probe_block_rows(caps["AccCap"])
    rungs = bfs._sort_rungs(caps["AccCap"])
    assert (caps["SC"] // B, caps["AccCap"] // QB,
            caps["AccCap"] // rungs[-1],
            caps["SC"] // caps["AccCap"], caps["AccCap"] // caps["FCap"]) \
        == (512, 64, 256, 2, 2)
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
    # each level's candidates on the smallest rung that holds them
    sorted_on = [rungs[bfs._sort_rung_index(cand, caps["AccCap"])]
                 for _, cand, _ in levels]
    assert len(set(sorted_on)) >= 3 and min(sorted_on) < caps["AccCap"]
    assert c["search.slots_sorted"] == sum(sorted_on)


def test_the_ooc_cells_spill_schedule_as_arithmetic():
    """`desk-ooc-4p8`: 4 procs / MaxMoney 8 under a cap of 2^20 rows
    (ISSUE 32, Motivation 4).  Four spills at levels 4 to 7, exactly
    `MAX_HOST_RUNS` runs (no compaction), inside the host budget (no disk);
    the counts are the uncapped reference's."""
    from jaxmc.backend.tiers import TieredSeen
    pins = _pins("transfer_scaled_4p8_ooc")
    sim = _cold_spills(pins["procs"], pins["max_money"], pins["seen_cap"])
    assert (sim["generated"], sim["distinct"], sim["diameter"]) == \
        (pins["generated"], pins["distinct"], pins["diameter"]) == \
        (4767576, 1859252, 12)
    for key, value in pins["tier"].items():
        if key != "what":
            assert sim[key] == value, key
    assert sim["spills"] == [[4, 398976], [5, 329472], [6, 374504],
                             [7, 341856]]
    assert (sim["spilled_keys"], sim["redone_rows"], sim["keys_probed"],
            sim["keys_dropped"]) == (1444808, 3367456, 1470128, 9852)
    # 968 of the keys dropped lie in two runs when they are asked for:
    # `tier_verify_share` reads 0.736 where nothing else passes a fence
    assert sim["keys_verified"] == 10820
    assert len(sim["spills"]) == TieredSeen.MAX_HOST_RUNS
    assert sim["cold_keys"] <= 1 << 22  # TieredSeen's default host budget
    # the tables admitted every distinct key once and every cold duplicate
    # once more (it sits in a run and in a later table: in a second run if
    # that table was spilled too, else hot at the end)
    assert sim["cold_keys"] + sim["hot_keys_at_end"] \
        == sim["distinct"] + sim["keys_dropped"]
    # a quarter of the states (ROADMAP B6's first cap) cannot hold
    with pytest.raises(AssertionError, match="breached"):
        _cold_spills(pins["procs"], pins["max_money"], 1 << 19)


def test_the_engine_spills_as_the_arithmetic_says(tmp_path, monkeypatch):
    """The resident engine at 4 procs / MaxMoney 2 under a cap of 2^12
    rows: three spills, 67 cold duplicates, and every `tier.*` counter
    equal to `_cold_spills`, on two searches of one session.  The sort's
    ladder cut to eight rungs: a level sorts the rung that holds its
    candidates, the three a spill rolled back both times."""
    pytest.importorskip("jax")
    from jaxmc.backend import bfs
    monkeypatch.setattr(bfs, "_SORT_RUNG_MIN", 64)
    sim = _cold_spills(4, 2, 1 << 12)
    assert sim["spills"] == [[4, 1728], [5, 1452], [6, 1499]]
    assert (sim["redone_rows"], sim["keys_probed"], sim["keys_dropped"],
            sim["cold_keys"]) == (11015, 5632, 67, 4679)
    caps = {"SC": 1 << 12, "FCap": 1 << 11, "AccCap": 1 << 13, "VC": 256}
    need = _needs([[f, c, dn] for f, c, dn, _ in sim["levels"]],
                  sim["levels"][0][0], caps["VC"], 1 << 12)
    assert all(need[k] <= caps[k] for k in need)
    tel = obs.Telemetry()
    with obs.use(tel):
        sess = CheckSession(SessionConfig(
            spec=TRANSFER, cfg=_toy_cfg(tmp_path, 4, 2), backend="jax",
            platform="cpu", resident=True, no_trace=True, res_caps=caps,
            seen_cap=1 << 12, chunk=64), tel=tel)
        for _ in range(2):
            before = dict(tel.counters)
            spans = {p["name"]: p["count"] for p in tel.phase_list()}
            res = sess.explore()
            assert (res.generated, res.distinct, res.diameter, res.ok) == \
                (sim["generated"], sim["distinct"], sim["diameter"], True)
            rise = {k: v - before.get(k, 0) for k, v in tel.counters.items()}
            assert rise["tier.spills"] == len(sim["spills"])
            for key in ("spilled_keys", "redone_rows", "keys_probed",
                        "keys_dropped"):
                assert rise["tier." + key] == sim[key], key
            assert tel.gauges["tier.occupancy"]["host"] == sim["cold_keys"]
            assert res.tiers["host_keys"] == sim["cold_keys"]
            # the keys that passed a run's fence and took the whole-row
            # compare: every key dropped did, none more than once a run —
            # and no two 128-bit fingerprints here share 64 leading bits,
            # so exactly the keys a run holds
            assert sim["keys_dropped"] <= rise["tier.keys_verified"] <= \
                sim["keys_probed"] * len(sim["spills"])
            assert rise["tier.keys_verified"] == sim["keys_verified"] == \
                res.tiers["keys_verified"]
            # `search.*` keep their meaning: new rows after the cold
            # duplicates are taken off, generated rows once
            assert rise["search.rows_new"] == \
                sim["distinct"] - sim["levels"][0][0]
            assert rise["search.rows_valid"] == \
                sim["generated"] - sim["levels"][0][0]
            rungs = bfs._sort_rungs(caps["AccCap"])
            sorted_on = [rungs[bfs._sort_rung_index(cand, caps["AccCap"])]
                         for _, cand, _, _ in sim["levels"]]
            assert len(rungs) == 8 and len(set(sorted_on)) >= 3
            assert rise["search.slots_sorted"] == sum(sorted_on) + sum(
                sorted_on[depth] for depth, _ in sim["spills"])
            # one span a spill; pull, keys and probe a probed level; a
            # push where a level had cold duplicates
            count = {p["name"]: p["count"] - spans.get(p["name"], 0)
                     for p in tel.phase_list()}
            probes = sum(1 for lv in sim["levels"][4:] if lv[2])
            assert count["tier.spill"] == len(sim["spills"])
            assert count["tier.pull"] == count["tier.keys"] == \
                count["tier.probe"] == probes
            assert count["tier.push"] == sum(
                1 for lv in sim["levels"] if lv[2] != lv[3])
        assert sess.engine._res_caps == caps  # nothing grew
    assert "tier.cap_breached" not in tel.gauges
