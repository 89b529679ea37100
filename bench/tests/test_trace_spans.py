"""xmeta.py (the metadata decoder) against ProfileData, and spans.py (device
seconds per kernel scope, idle seconds per program span) on the two traces
recorded on the chip and on hand-made intervals."""

import json
import os

import pytest

import lib
import reduce as R
import spans as S
import xmeta as X

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PLAIN = os.path.join(DATA, "small_tpu.xplane.pb")           # no jaxmc.* name
SCOPED = os.path.join(DATA, "small_tpu_scoped.xplane.pb")   # record_trace_scoped.py
NEW = ("expand_device_s", "sort_device_s", "probe_device_s",
       "scatter_device_s", "compact_device_s", "unscoped_device_share",
       "seed_idle_s", "sync_idle_s", "unattributed_idle_s", "sort_fill",
       "dispatch_idle_s", "seen_fill")


@pytest.mark.parametrize("path", [PLAIN, SCOPED])
def test_xmeta_reads_what_profiledata_reads(path):
    """Every plane, line and event: names from the metadata table, starts
    and durations to the nanosecond ProfileData rounds to."""
    from jax.profiler import ProfileData
    mine, n = X.read(path), 0
    theirs = list(ProfileData.from_file(path).planes)
    assert [p["name"] for p in mine] == [p.name for p in theirs]
    for pm, pt in zip(mine, theirs):
        lines = list(pt.lines)
        assert [ln["name"] for ln in pm["lines"]] == [ln.name for ln in lines]
        for lm, lt in zip(pm["lines"], lines):
            events = list(lt.events)
            assert len(lm["events"]) == len(events)
            for (mid, start, dur), ev in zip(lm["events"], events):
                assert pm["event_metadata"][mid]["name"] == ev.name
                assert abs(start - ev.start_ns) < 1
                assert abs(dur - ev.duration_ns) < 1
                n += 1
    assert n > 200


def test_xmeta_surfaces_the_op_name():
    """The one metadata stat it keeps, from both kinds a string stat comes
    in (`str` and `ref`), and none of the others."""
    dev = [p for p in X.read(SCOPED) if p["name"] == "/device:TPU:0"][0]
    by_op = {R.op_name(m["name"]): m
             for m in dev["event_metadata"].values()}
    sort = by_op["sort (s32[262144],s32[262144])"]
    assert sort["tf_op"] == \
        "jit(search)/while/body/jaxmc.merge.sort/jit(sort)/sort:"
    assert set(sort) == {"name", "tf_op"}
    assert sum("tf_op" in m for m in by_op.values()) == 5


def test_xmeta_follows_a_ref_stat(tmp_path):
    """A string stat may come as `ref`: the id of a stat_metadata entry
    whose NAME is the string (hand-encoded; the recorded traces use `str`)."""
    def vi(n):
        out = b""
        while n > 0x7F:
            out, n = out + bytes([n & 0x7F | 0x80]), n >> 7
        return out + bytes([n])

    def ld(num, body):
        return vi(num << 3 | 2) + vi(len(body)) + body

    def iv(num, n):
        return vi(num << 3) + vi(n)

    def entry(num, key, body):
        return ld(num, iv(1, key) + ld(2, body))

    plane = ld(2, b"/device:TPU:0") \
        + entry(5, 1, iv(1, 1) + ld(2, b"tf_op")) \
        + entry(5, 2, iv(1, 2) + ld(2, b"jit(f)/jaxmc.keys/add:")) \
        + entry(4, 7, iv(1, 7) + ld(2, b"%fusion.1") +
                ld(5, iv(1, 1) + iv(7, 2))) \
        + ld(3, ld(2, b"XLA Ops") + iv(3, 5) +
             ld(4, iv(1, 7) + iv(2, 2000) + iv(3, 9000)))
    path = tmp_path / "ref.xplane.pb"
    path.write_bytes(ld(1, plane))
    (got,) = X.read(str(path))
    assert got["event_metadata"] == {
        7: {"name": "%fusion.1", "tf_op": "jit(f)/jaxmc.keys/add:"}}
    assert got["lines"] == [{"name": "XLA Ops", "events": [(7, 7.0, 9.0)]}]


def test_scope_of_takes_the_innermost_jaxmc_component():
    assert S.scope_of("jit(run)/while/body/jaxmc.merge.scatter/"
                      "jaxmc.merge.sort/sort:") == "jaxmc.merge.sort"
    assert S.scope_of("jit(step)/jaxmc.expand/jit(remainder)/select_n:") \
        == "jaxmc.expand"
    assert S.scope_of("jit(search)/while/body/jit(sort)/sort:") == "unscoped"
    assert S.scope_of(None) == S.scope_of("") == "unscoped"


def test_span_idle_cuts_gaps_at_span_borders():
    spans = [("jaxmc.search.seed", 10, 30), ("jaxmc.search.dispatch", 30, 90),
             ("jaxmc.level.sync", 40, 50)]
    got = S.span_idle([(0, 45), (60, 70), (95, 100)], spans)
    assert got == {"unattributed": 10 + 5, "jaxmc.search.seed": 20,
                   "jaxmc.search.dispatch": 10 + 10, "jaxmc.level.sync": 5}
    assert sum(got.values()) == 45 + 10 + 5


def test_scoped_plus_unscoped_is_the_search_busy_time():
    an, red = S.analyze(SCOPED), R.reduce_trace(SCOPED)
    assert an["searches"] == red["searches_traced"] == 3
    assert sum(an["scope_s"].values()) == pytest.approx(
        red["search_busy_s"], rel=1e-3)
    assert an["search_busy_s"] == pytest.approx(red["search_busy_s"],
                                                rel=1e-3)
    # the sort is nearly all of it, under its scope; the remainder fusion
    # and the reduction outside the scopes are there too
    assert an["scope_s"]["jaxmc.merge.sort"] > 0.9 * an["search_busy_s"]
    assert 0 < an["scope_s"]["jaxmc.expand"] < an["scope_s"]["unscoped"]


def test_attributed_plus_unattributed_is_the_idle_inside_the_searches():
    an = S.analyze(SCOPED)
    host = R.read_planes(SCOPED)["host"]
    inside = sum(e - s for s, e in R.spans_named(host, "bench.search")) / 1e9
    assert sum(an["idle_s"].values()) == pytest.approx(
        inside - an["search_busy_s"], rel=1e-3)
    # the sleeps of the recorder, to the millisecond by which the device's
    # clock and the host's disagree in one trace
    idle = an["idle_s"]
    assert idle["jaxmc.search.seed"] == pytest.approx(3 * 0.003, abs=0.003)
    assert idle["unattributed"] == pytest.approx(3 * 0.002, abs=0.003)
    assert idle["jaxmc.search.dispatch"] > 0   # launch + fetch latency


def _run(trace_path, tmp_path, counters=None):
    """A reader's `run` over a recorded trace laid out as a trace dir."""
    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True, exist_ok=True)
    (d / "t.xplane.pb").write_bytes(open(trace_path, "rb").read())
    a, b = counters or ({}, {})
    return {"out": {"trace_dir": str(tmp_path),
                    "artifacts": {"at_window": {"counters": a},
                                  "after": {"counters": b}}},
            "trace": R.reduce_trace(trace_path), "bench_dir": lib.BENCH}


def _read(name, run):
    return lib.load_module(os.path.join(lib.BENCH, "layers", name + ".py"),
                           "bench_layer_" + name).read(run)


def test_a_trace_without_a_jaxmc_name_reads_none_everywhere(tmp_path):
    """The parent commit's trace, and the parent's counters: every new
    reader finds nothing to read and the metric is left out."""
    run = _run(PLAIN, tmp_path)
    assert S.analyze(PLAIN)["named"] is False
    for name in NEW:
        assert _read(name, run) is None, name
    assert _read("sort_fill", {"out": {}}) is None
    assert _read("seen_fill", {"out": {}}) is None


def test_executables_from_a_stale_cache_give_no_kernel_seconds(
        tmp_path, monkeypatch):
    """The scopes are debug info, which jax's cache key strips: a cache an
    older commit filled serves executables without them.  The host spans
    are there, so the idle readers read; `unscoped_device_share` says 100
    and the five `*_device_s` say None, not 0."""
    monkeypatch.setattr(S, "scope_of", lambda tf_op: S.UNSCOPED)
    S.analyze.cache_clear()
    try:
        run = _run(SCOPED, tmp_path)
        an = S.of_run(run)
        assert an["named"] and not an["scoped"]
        for name in NEW[:5]:
            assert _read(name, run) is None, name
        assert _read("unscoped_device_share", run) == 100.0
        assert _read("seed_idle_s", run) == pytest.approx(0.003, abs=0.001)
    finally:
        S.analyze.cache_clear()


def test_the_readers_on_the_scoped_trace(tmp_path):
    run = _run(SCOPED, tmp_path,
               ({"search.rows_valid": 100, "search.slots_sorted": 1000,
                 "search.rows_new": 10, "search.seen_slots": 2000},
                {"search.rows_valid": 400, "search.slots_sorted": 5000,
                 "search.rows_new": 70, "search.seen_slots": 10000}))
    an = S.analyze(SCOPED)
    assert _read("sort_device_s", run) == pytest.approx(
        an["scope_s"]["jaxmc.merge.sort"] / 3)
    assert _read("expand_device_s", run) == pytest.approx(
        an["scope_s"]["jaxmc.expand"] / 3)
    assert _read("probe_device_s", run) == 0.0
    assert _read("scatter_device_s", run) == 0.0
    assert _read("compact_device_s", run) == 0.0
    five = sum(_read(n, run) for n in NEW[:5])
    share = _read("unscoped_device_share", run)
    assert 0 < share < 2
    assert five / (1 - share / 100) == pytest.approx(
        run["trace"]["search_busy_s"] / 3, rel=1e-3)
    assert _read("seed_idle_s", run) == pytest.approx(0.003, abs=0.001)
    assert _read("dispatch_idle_s", run) > 0
    assert _read("sync_idle_s", run) == 0.0
    assert _read("unattributed_idle_s", run) == pytest.approx(0.002,
                                                              abs=0.001)
    assert _read("sort_fill", run) == pytest.approx(100 * 300 / 4000)
    assert _read("seen_fill", run) == pytest.approx(100 * 60 / 8000)


def test_the_manifest_names_a_reader_for_each_new_metric():
    bm = lib.load_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
    tail = bm["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == list(NEW)
    cells = [w["name"] for w in bm["workloads"]]
    for m in tail:
        assert m["workloads"] == cells and m["moves"] == "states_per_s"
        assert os.path.isfile(os.path.join(lib.BENCH, "layers",
                                           m["name"] + ".py"))
        assert m["layer"] in ("kernels", "engines")
    for w in cells:
        names = {m["name"] for m in lib.resolve(w)["per_layer"]}
        assert names >= set(NEW)
    assert len(json.dumps(bm)) < 64 * 1024
