"""The plain reference against the pins, the seed's traffic, the control."""

import os
import re

import pytest

import control
import lib

REF = lib.load_module(os.path.join(lib.BENCH, "reference",
                                   "transfer_scaled.py"), "ref_under_test")


@pytest.mark.parametrize("pins", ["transfer_scaled", "transfer_scaled_4p8"])
def test_reference_recomputes_the_pins(pins):
    pin = lib.load_json(os.path.join(lib.BENCH, "pins", pins + ".json"))
    cfg = open(os.path.join(lib.ROOT, pin["cfg"])).read()
    n, m, invs = REF.parse_cfg(cfg)
    assert (n, m, invs) == (pin["procs"], pin["max_money"],
                            ["AliceBounded"])
    got = REF.explore(n, m)
    for key in ("generated", "distinct", "diameter", "levels"):
        assert got[key] == pin[key], key
    assert got["ok"] is True
    caps, lv = pin["res_caps"], pin["levels"]
    seen = 0
    for frontier, generated, new in lv:
        seen += frontier
        assert frontier <= caps["FCap"]
        assert generated + caps["VC"] <= caps["AccCap"]
        assert seen + generated <= caps["SC"]


def test_one_process_by_hand():
    # 1 process, MaxMoney 2: two initial states (money 1 or 2); each walks
    # check -> debit -> credit -> done and then stutters (Terminating):
    # 2 x 4 distinct, one successor per state = 2 + 8 generated, depth 3.
    got = REF.explore(1, 2)
    assert got["distinct"] == 8 and got["generated"] == 10
    assert got["diameter"] == 3 and got["ok"] is True
    assert got["levels"] == [[2, 2, 2], [2, 2, 2], [2, 2, 2], [2, 2, 0]]


def test_two_processes_race():
    # 2 processes, MaxMoney 1 (money = [1, 1], alice = 1): both may pass
    # the check before either debits, so alice reaches -1: the race the
    # README describes.  29 / 19 is also what jaxmc's exact interpreter
    # prints for this cfg (cross-checked by hand in PR 23).
    got = REF.explore(2, 1)
    assert got["levels"][0] == [1, 2, 2]
    assert (got["generated"], got["distinct"], got["diameter"]) == \
        (29, 19, 6)


@pytest.mark.parametrize("seed", [0, 1, 7, 2 ** 31 + 12345])
def test_the_seed_rewrites_the_cfg_and_keeps_the_model(seed):
    src = open(os.path.join(lib.BENCH, "specs",
                            "transfer_scaled_4p8.cfg")).read()
    out = lib.permute_cfg(src, seed)
    assert out == lib.permute_cfg(src, seed)
    assert REF.parse_cfg(out) == REF.parse_cfg(src)
    words = lambda t: sorted(re.sub(r"[{},]", " ", t).split())  # noqa: E731
    assert words(out) == words(src)
    assert out.splitlines()[:3] == src.splitlines()[:3]


def test_some_seed_changes_the_order():
    src = open(os.path.join(lib.BENCH, "specs",
                            "transfer_scaled.cfg")).read()
    assert len({lib.permute_cfg(src, s) for s in range(12)}) > 3


def test_stamped_spec_is_the_same_module_with_a_new_hash():
    src = open(os.path.join(lib.BENCH, "specs",
                            "transfer_scaled.tla")).read()
    a, b = lib.stamp_spec(src, 1), lib.stamp_spec(src, 2)
    assert a != b and a.splitlines()[0] == src.splitlines()[0]
    strip = lambda t: [ln for ln in t.splitlines()       # noqa: E731
                       if "bench traffic, seed" not in ln]
    assert strip(a) == src.splitlines() == strip(b)


@pytest.mark.parametrize("drop", [1, 4, 8])
def test_the_control_comes_out_not_correct(drop, capsys):
    """Narrowed dedup keys, at a test's size (2 procs, MaxMoney 5), through
    the harness's own comparison."""
    mix = {"reference": "transfer_scaled"}
    cfg = "INVARIANT AliceBounded\nCONSTANTS\n  Procs = {a, b}\n" \
          "  MaxMoney = 5\n"
    ref = lib.reference_answer(mix, cfg)
    assert lib.compare(dict(ref, truncated=False), ref, "sound") is True
    got = control.control_answer(mix, cfg, drop)
    got["truncated"] = False
    assert got["distinct"] < ref["distinct"]
    assert lib.compare(got, ref, "control") is False


def test_the_control_at_the_cells_own_size():
    assert control.main(["--workload", "desk-recheck-3p",
                         "--seeds", "1,2,3"]) == 0
