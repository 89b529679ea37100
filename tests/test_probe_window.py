"""The resident engine with its probe on windows of the table (ISSUE 45).

Where the seen table has more rows than `bfs._probe_window_rows(SC)`, a
block of `bfs._seen_probe`'s sorted queries searches the window of the
table its answers span (tests/test_rank_merge.py has the kernel's
contract).  Here the engine at toy size with the window's floor and the
query blocks lowered, so that a search runs blocks that take the window
and blocks that take the whole table: its counts, verdict and trace are
what the same engine answers with no window at all (W >= SC: the program
up to PR 44) and what the plain reference says; `search.slots_windowed`
counts blocks x QB beside `search.slots_probed`, and no engine but the
resident one, and no resident program without a window, publishes it."""

import pytest

pytest.importorskip("jax")

from jaxmc import obs  # noqa: E402
from jaxmc.backend import bfs  # noqa: E402
from jaxmc.session import CheckSession, SessionConfig  # noqa: E402

from test_bench_pins import TRANSFER, _reference, _toy_cfg  # noqa: E402
from test_resident_trace import (  # noqa: E402,F401
    VIOLATION, _cfg, _plain, reference)
from test_sort_ladder import _answer  # noqa: E402

WINDOW, BLOCK_MIN = 256, 64
CAPS = {"SC": 1 << 14, "FCap": 1 << 11, "AccCap": 1 << 13, "VC": 256}
COUNTED = ("search.rows_valid", "search.rows_new", "search.slots_probed",
           "search.slots_merged", "search.slots_sorted",
           "search.seen_slots")


@pytest.fixture(autouse=True)
def _toy_blocks(monkeypatch):
    monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")
    monkeypatch.setattr(bfs, "_PROBE_BLOCK_MIN", BLOCK_MIN)
    monkeypatch.setattr(bfs, "_PROBE_WINDOW_ROWS", WINDOW)


def _explore(spec, cfg, **opts):
    tel = obs.Telemetry()
    with obs.use(tel):
        res = CheckSession(SessionConfig(
            spec=spec, cfg=cfg, backend="jax", platform="cpu", chunk=64,
            **opts), tel=tel).explore()
    return res, tel


@pytest.mark.parametrize("case", ["plain", "seen_overflow_redo"])
def test_resident_counts_with_and_without_the_window(case, tmp_path,
                                                     monkeypatch):
    """4 procs / MaxMoney 2 (19,101 generated, 13 levels): the plain
    reference's counts with windows of 256 rows over a table of 2^14 —
    also where a table that starts too small rolls levels back and
    grows (a rolled-back level's windowed blocks count: work done) —
    and every counter the program without a window reports, unmoved."""
    want = _reference().explore(4, 2)
    caps = dict(CAPS, SC=1 << 9) if case == "seen_overflow_redo" else CAPS
    opts = dict(resident=True, no_trace=True)
    cfg = _toy_cfg(tmp_path, 4, 2)
    res, tel = _explore(TRANSFER, cfg, res_caps=dict(caps), **opts)
    qb = bfs._probe_block_rows(caps["AccCap"])
    assert qb == caps["AccCap"] // 64
    c = tel.counters
    assert 0 < c["search.slots_windowed"] <= c["search.slots_probed"]
    assert c["search.slots_windowed"] % qb == 0
    monkeypatch.setattr(bfs, "_PROBE_WINDOW_ROWS", 1 << 20)
    whole, tel1 = _explore(TRANSFER, cfg, res_caps=dict(caps), **opts)
    assert _answer(res) == _answer(whole) == \
        (True, want["generated"], want["distinct"], want["diameter"], None)
    assert "search.slots_windowed" not in tel1.counters
    for name in COUNTED:
        assert c[name] == tel1.counters[name], name


def test_resident_violation_and_its_trace_with_and_without_the_window(
        tmp_path, reference, monkeypatch):
    """The violating cfg at 2 procs / MaxMoney 3, traces kept: the same
    verdict, counts and 7-state trace with windows of 64 rows and with
    none, and the trace a behaviour by the plain reference."""
    monkeypatch.setattr(bfs, "_PROBE_WINDOW_ROWS", 64)
    cfg = _cfg(tmp_path, ("p1", "p2"), 3)
    caps = {"SC": 4096, "FCap": 1024, "AccCap": 4096, "VC": 256}
    res, tel = _explore(VIOLATION, cfg, resident=True, res_caps=dict(caps))
    c = tel.counters
    assert 0 < c["search.slots_windowed"] <= c["search.slots_probed"]
    monkeypatch.setattr(bfs, "_PROBE_WINDOW_ROWS", caps["SC"])
    whole, tel1 = _explore(VIOLATION, cfg, resident=True,
                           res_caps=dict(caps))
    assert "search.slots_windowed" not in tel1.counters
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
def test_only_the_resident_engine_counts_windowed_slots(engine, tmp_path,
                                                        monkeypatch):
    """The level engine's step and the mesh's shards run the same
    merge, and with the floor this low (their tables here are 4,096
    rows and 256 a shard) their blocks take windows too; they carry no
    such count (in every cell their tables are no larger than W) and
    publish none."""
    monkeypatch.setattr(bfs, "_PROBE_WINDOW_ROWS", 64)
    want = _reference().explore(3, 2)
    opts = dict(devices=4) if engine == "mesh" else {}
    res, tel = _explore(TRANSFER, _toy_cfg(tmp_path, 3, 2), **opts)
    assert (res.ok, res.generated, res.distinct, res.diameter) == \
        (True, want["generated"], want["distinct"], want["diameter"])
    assert tel.counters["search.slots_probed"] > 0
    assert "search.slots_windowed" not in tel.counters
