"""`compact_fill` (added in PR 46): the reader on hand-made counters, `None`
where the program has no `search.slots_compacted`, and its manifest entry."""

import os

import lib

CELLS = ("desk-recheck-4p8", "desk-recheck-3p", "desk-deep-4p",
         "desk-ooc-4p8", "desk-violation-4p")


def _read(run):
    return lib.load_module(os.path.join(lib.BENCH, "layers",
                                        "compact_fill.py"),
                           "bench_layer_compact_fill").read(run)


def _run(at_window, after):
    return {"out": {"artifacts": {"at_window": {"counters": at_window},
                                  "after": {"counters": after}}}}


def test_reads_the_rise_over_the_window():
    # the warm-up's counts are not the window's: 9,373,283 new rows in 78
    # blocks of 2^17 slots a search, three searches
    rb = 1 << 17
    at = {"search.rows_new": 9373283, "search.slots_compacted": 78 * rb,
          "search.slots_probed": 190 * rb}
    after = {"search.rows_new": 4 * 9373283,
             "search.slots_compacted": 4 * 78 * rb,
             "search.slots_probed": 4 * 190 * rb}
    assert _read(_run(at, after)) == 100.0 * 9373283 / (78 * rb)
    # a counter that first rose inside the window; every slot a row
    assert _read(_run({}, {"search.rows_new": 40,
                           "search.slots_compacted": 40})) == 100.0
    # a CONSTRAINT that kept nothing: 0, not None (the program counts)
    assert _read(_run({}, {"search.rows_new": 0,
                           "search.slots_compacted": 64})) == 0.0


def test_none_where_there_is_nothing_to_read():
    # the parent's counters (and the level engine's, the mesh's); no
    # artifacts; nothing gathered
    parent = {"search.rows_new": 166, "search.slots_probed": 4096}
    assert _read(_run({}, parent)) is None
    assert _read({}) is None and _read({"out": {}}) is None
    assert _read(_run({}, {"search.rows_new": 0,
                           "search.slots_compacted": 0})) is None


def test_the_manifest_entry():
    bm = lib.load_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
    # found by name, not by place: later PRs append after it
    (entry,) = [m for m in bm["per_layer"] if m["name"] == "compact_fill"]
    assert entry == {"name": "compact_fill", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "states_per_s", "workloads": list(CELLS)}
    for w in bm["workloads"]:
        names = [m["name"] for m in lib.resolve(w["name"])["per_layer"]]
        assert ("compact_fill" in names) == (w["name"] in CELLS), w["name"]
