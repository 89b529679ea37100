"""`window_fill` (added in PR 45): the reader on hand-made counters, `None`
where the program has no `search.slots_windowed`, and its manifest entry."""

import os

import lib

CELLS = ("desk-recheck-4p8", "desk-deep-4p", "desk-violation-4p")


def _read(run):
    return lib.load_module(os.path.join(lib.BENCH, "layers",
                                        "window_fill.py"),
                           "bench_layer_window_fill").read(run)


def _run(at_window, after):
    return {"out": {"artifacts": {"at_window": {"counters": at_window},
                                  "after": {"counters": after}}}}


def test_reads_the_rise_over_the_window():
    # the warm-up's counts are not the window's: 176 of 190 blocks of
    # 2^17 slots a search, three searches
    qb = 1 << 17
    at = {"search.slots_windowed": 176 * qb, "search.slots_probed": 190 * qb,
          "search.rows_valid": 24014861}
    after = {"search.slots_windowed": 4 * 176 * qb,
             "search.slots_probed": 4 * 190 * qb,
             "search.rows_valid": 4 * 24014861}
    assert _read(_run(at, after)) == 100.0 * 176 / 190
    # a counter that first rose inside the window; every block windowed
    assert _read(_run({}, {"search.slots_windowed": 40,
                           "search.slots_probed": 40})) == 100.0
    # no block took the window: 0, not None (the program HAS one)
    assert _read(_run({}, {"search.slots_windowed": 0,
                           "search.slots_probed": 40})) == 0.0


def test_none_where_there_is_nothing_to_read():
    # the parent's counters (and a program whose table has no window);
    # no artifacts; nothing searched
    parent = {"search.rows_valid": 247, "search.slots_probed": 4096}
    assert _read(_run({}, parent)) is None
    assert _read({}) is None and _read({"out": {}}) is None
    assert _read(_run({}, {"search.slots_windowed": 0,
                           "search.slots_probed": 0})) is None


def test_the_manifest_entry():
    bm = lib.load_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
    # found by name, not by place: later PRs append after it
    (entry,) = [m for m in bm["per_layer"] if m["name"] == "window_fill"]
    assert entry == {"name": "window_fill", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "states_per_s", "workloads": list(CELLS)}
    for w in bm["workloads"]:
        names = [m["name"] for m in lib.resolve(w["name"])["per_layer"]]
        assert ("window_fill" in names) == (w["name"] in CELLS), w["name"]
