"""`newkey_fill` (added in PR 48): the reader on hand-made counters, `None`
where the program has no `search.slots_keyed`, and its manifest entry."""

import os

import lib

CELLS = ("desk-deep-4p", "desk-violation-4p", "desk-symmetry-5p")


def _read(run):
    return lib.load_module(os.path.join(lib.BENCH, "layers",
                                        "newkey_fill.py"),
                           "bench_layer_newkey_fill").read(run)


def _run(at_window, after):
    return {"out": {"artifacts": {"at_window": {"counters": at_window},
                                  "after": {"counters": after}}}}


def test_reads_the_rise_over_the_window():
    # the warm-up's counts are not the window's: 9,373,283 new keys in 78
    # blocks of 2^17 slots a search, three searches
    qb = 1 << 17
    at = {"search.rows_new": 9373283, "search.slots_keyed": 78 * qb,
          "search.slots_merged": 2281 << 15}
    after = {"search.rows_new": 4 * 9373283,
             "search.slots_keyed": 4 * 78 * qb,
             "search.slots_merged": 4 * 2281 << 15}
    assert _read(_run(at, after)) == 100.0 * 9373283 / (78 * qb)
    # a counter that first rose inside the window; every slot a key
    assert _read(_run({}, {"search.rows_new": 40,
                           "search.slots_keyed": 40})) == 100.0


def test_none_where_there_is_nothing_to_read():
    # the parent's counters (and the whole form's, the level engine's,
    # the mesh's); no artifacts; nothing gathered
    parent = {"search.rows_new": 166, "search.slots_merged": 32768,
              "search.slots_compacted": 256}
    assert _read(_run({}, parent)) is None
    assert _read({}) is None and _read({"out": {}}) is None
    assert _read(_run({}, {"search.rows_new": 0,
                           "search.slots_keyed": 0})) is None


def test_the_manifest_entry():
    bm = lib.load_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
    # found by name, not by place: later PRs append after it
    (entry,) = [m for m in bm["per_layer"] if m["name"] == "newkey_fill"]
    assert entry["workloads"][:len(CELLS)] == list(CELLS)
    assert dict(entry, workloads=None) == {
        "name": "newkey_fill", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "states_per_s", "workloads": None}
    for w in bm["workloads"]:
        names = [m["name"] for m in lib.resolve(w["name"])["per_layer"]]
        assert ("newkey_fill" in names) == (w["name"] in entry["workloads"])
