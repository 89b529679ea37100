"""The engines with the merge's build on windows of the new keys (ISSUE 48).

Where a level has more key slots than `bfs._BUILD_WHOLE_KEYS`, `_rank_merge`
compacts the level's new keys once and a block of seen2 reads a slice of
them (tests/test_rank_merge.py has the kernel's contract).  Here the
resident engine at toy size with that floor and the query blocks lowered:
its counts, verdict and trace are what the same engine answers in the whole
form and what the plain reference says; `search.slots_keyed` is the sum over
the levels run of what the ONE rule (`bfs._compact_blocks`) gives for each
level's new keys, beside the gauge `merge.build_form`; the level engine and
the mesh's shards run the window form to the same answers and count nothing.
Last, the forms the benchmark's pinned capacities choose: the window in the
three cells of 2^23 key slots, and in every other the text of the function
up to PR 47."""

import pytest

pytest.importorskip("jax")

from jaxmc import obs  # noqa: E402
from jaxmc.backend import bfs  # noqa: E402
from jaxmc.session import CheckSession, SessionConfig  # noqa: E402

from test_bench_pins import (  # noqa: E402
    RESIDENT_PINS, TRANSFER, _pins, _reference, _toy_cfg)
from test_rank_merge import _lowered_merge, _whole_rank_merge  # noqa: E402
from test_resident_trace import (  # noqa: E402,F401
    VIOLATION, _cfg, _plain, reference)
from test_sort_ladder import _answer, _levels_run  # noqa: E402

# the floor of the window form and of a query block at toy size
FLOOR, BLOCK_MIN = 256, 64
CAPS = {"SC": 1 << 14, "FCap": 1 << 11, "AccCap": 1 << 13, "VC": 256}
COUNTED = ("search.rows_valid", "search.rows_new", "search.slots_probed",
           "search.slots_merged", "search.slots_sorted",
           "search.seen_slots", "search.slots_compacted")


@pytest.fixture(autouse=True)
def _toy_blocks(monkeypatch):
    monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")
    monkeypatch.setattr(bfs, "_PROBE_BLOCK_MIN", BLOCK_MIN)
    monkeypatch.setattr(bfs, "_BUILD_WHOLE_KEYS", FLOOR)


def _explore(spec, cfg, **opts):
    tel = obs.Telemetry()
    with obs.use(tel):
        res = CheckSession(SessionConfig(
            spec=spec, cfg=cfg, backend="jax", platform="cpu", chunk=64,
            **opts), tel=tel).explore()
    return res, tel


@pytest.mark.parametrize("probe_window", [None, 256],
                         ids=["build_window", "both_windows"])
@pytest.mark.parametrize("case", ["plain", "seen_overflow_redo"])
def test_resident_counts_and_slots_keyed_by_the_one_rule(
        case, probe_window, tmp_path, monkeypatch):
    """4 procs / MaxMoney 2 (19,101 generated, 13 levels) with 2^13 key
    slots over a floor of 256 — also beside the probe's window (the
    summary then ends in two words), and where a table that starts too
    small rolls levels back and grows (a rolled-back level compacted its
    new keys: work done)."""
    if probe_window:
        monkeypatch.setattr(bfs, "_PROBE_WINDOW_ROWS", probe_window)
    want = _reference().explore(4, 2)
    news = [new for _, _, new in want["levels"]]
    caps = dict(CAPS, SC=1 << 9) if case == "seen_overflow_redo" else CAPS
    opts = dict(resident=True, no_trace=True)
    cfg = _toy_cfg(tmp_path, 4, 2)
    res, tel = _explore(TRANSFER, cfg, res_caps=dict(caps), **opts)
    qb = bfs._probe_block_rows(caps["AccCap"])
    assert qb == caps["AccCap"] // 64
    c = tel.counters
    assert tel.gauges["merge.build_form"] == "window"
    assert ("search.slots_windowed" in c) == bool(probe_window)
    by_rule = sum(bfs._compact_blocks(new, caps["AccCap"], qb)
                  for new in news) * qb
    assert by_rule == sum(-(-new // qb) for new in news) * qb
    if case == "plain":
        assert _levels_run(tel) == list(range(len(news)))
        assert c["search.rows_new"] == sum(news) <= \
            c["search.slots_keyed"] == by_rule
    else:
        assert len(_levels_run(tel)) > len(news)
        assert c["search.slots_keyed"] > by_rule
        assert c["search.slots_keyed"] % qb == 0
    monkeypatch.setattr(bfs, "_BUILD_WHOLE_KEYS", caps["AccCap"])
    whole, tel1 = _explore(TRANSFER, cfg, res_caps=dict(caps), **opts)
    assert _answer(res) == _answer(whole) == \
        (True, want["generated"], want["distinct"], want["diameter"], None)
    assert tel1.gauges["merge.build_form"] == "whole"
    assert "search.slots_keyed" not in tel1.counters
    for name in COUNTED + ("search.slots_windowed",) * bool(probe_window):
        assert c[name] == tel1.counters[name], name


def test_resident_violation_and_its_trace_in_both_forms(
        tmp_path, reference, monkeypatch):
    """The violating cfg at 2 procs / MaxMoney 3, traces kept (the
    summary carries the log's words before the two windows'): the same
    verdict, counts and 7-state trace in the window form and the whole,
    and the trace a behaviour by the plain reference."""
    monkeypatch.setattr(bfs, "_PROBE_WINDOW_ROWS", 64)
    cfg = _cfg(tmp_path, ("p1", "p2"), 3)
    caps = {"SC": 4096, "FCap": 1024, "AccCap": 4096, "VC": 256}
    res, tel = _explore(VIOLATION, cfg, resident=True, res_caps=dict(caps))
    c = tel.counters
    assert 0 < c["search.rows_new"] <= c["search.slots_keyed"]
    assert 0 < c["search.slots_windowed"] <= c["search.slots_probed"]
    monkeypatch.setattr(bfs, "_BUILD_WHOLE_KEYS", caps["AccCap"])
    whole, tel1 = _explore(VIOLATION, cfg, resident=True,
                           res_caps=dict(caps))
    assert "search.slots_keyed" not in tel1.counters
    assert tel1.counters["search.slots_windowed"] == \
        c["search.slots_windowed"]
    want = reference.explore(2, 3)
    for got in (res, whole):
        assert (got.violation.kind, got.violation.name) == \
            ("invariant", "NoMoneyCreated")
        assert (got.generated, got.distinct, got.diameter) == \
            (want["generated"], want["distinct"], want["diameter"])
    assert _answer(res) == _answer(whole)
    assert len(res.violation.trace) == 7
    states, labels = _plain(res.violation.trace)
    ok, why = reference.check_trace(states, labels, 2, 3, "NoMoneyCreated",
                                    min_len=7)
    assert ok, why


@pytest.mark.parametrize("engine", ["level", "mesh"])
def test_only_the_resident_engine_counts_keyed_slots(engine, tmp_path,
                                                     monkeypatch):
    """The level engine's step and the mesh's shards run the same merge,
    and over a floor this low (a shard here has 256 key slots) in the
    window form: the reference's counts, the gauge says so, and no count
    of the compaction's blocks (in every cell their key slots are under
    the floor)."""
    monkeypatch.setattr(bfs, "_BUILD_WHOLE_KEYS", 16)
    want = _reference().explore(3, 2)
    opts = dict(devices=4) if engine == "mesh" else {}
    res, tel = _explore(TRANSFER, _toy_cfg(tmp_path, 3, 2), **opts)
    assert (res.ok, res.generated, res.distinct, res.diameter) == \
        (True, want["generated"], want["distinct"], want["diameter"])
    assert tel.gauges["merge.build_form"] == "window"
    assert tel.counters["search.slots_probed"] > 0
    assert "search.slots_keyed" not in tel.counters


# ------------------------------------------- the forms the pins choose
#
# The key slots of each cell's merge, from bench/pins: AccCap for the
# resident programs (the served cells run transfer_scaled's and
# transfer_scaled_4p8's), the valid-candidate capacity VC a shard for
# the mesh, and for the level engine (desk-default-3p) the candidate
# grid of its largest step, 10 expand instances x FC 2^16.
WINDOW_PINS = {"transfer_scaled_4p", "transfer_symmetry_5p",
               "transfer_violation_4p", "transfer_retry_4p"}
LEVEL_ENGINE_3P = "the level engine, 3p"


def _merge_shape(pins):
    """(key slots, table rows, multikey, sorted on the ladder)."""
    if pins == LEVEL_ENGINE_3P:
        return 10 * (1 << 16), 1 << 20, True, False
    caps = _pins(pins)["res_caps"]
    if "AccCap" in caps:
        return caps["AccCap"], caps["SC"], False, True
    return caps["VC"], caps["SC"], True, False


@pytest.mark.parametrize("pins", RESIDENT_PINS + ["transfer_scaled_4p8_mesh",
                                                 LEVEL_ENGINE_3P])
def test_the_pins_choose_the_form_by_their_key_slots(pins, monkeypatch):
    """A static function of N alone, no cell's name: 2^23 key slots build
    from windows of the new keys; 2^21 and under (desk-recheck-4p8, the 3p
    cells, desk-ooc-4p8, the mesh's shards, the level engine's step) lower
    to the text of the function up to PR 47, a compaction more where they
    do not."""
    monkeypatch.undo()  # the real floor and the real blocks
    n, sc, multikey, ladder = _merge_shape(pins)
    form = bfs._build_form(n)
    assert form == ("window" if pins in WINDOW_PINS else "whole")
    assert (form == "window") == (n == 1 << 23)
    text = _lowered_merge(bfs._rank_merge, n, sc, 5, multikey, ladder)
    had = _lowered_merge(_whole_rank_merge, n, sc, 5, multikey, ladder)
    if form == "whole":
        assert text == had
    else:
        assert text.count("stablehlo.while") == \
            had.count("stablehlo.while") + 1
        # the build's new rows: a slice of B rows of the compacted keys,
        # and no gather from all N of the sorted ones
        b = bfs._merge_block_rows(sc)
        assert f"tensor<{n}x5xi32>, tensor<{b}x1xi32>" in had
        assert f"tensor<{n}x5xi32>, tensor<{b}x1xi32>" not in text
