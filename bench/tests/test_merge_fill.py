"""`merge_fill` (added in PR 29): the reader on hand-made counters, `None`
where the program has no `search.slots_merged`, and its manifest entry."""

import os

import lib

CELLS = ("desk-recheck-4p8", "desk-default-3p", "desk-recheck-3p",
         "mesh-recheck-4p")


def _read(run):
    return lib.load_module(os.path.join(lib.BENCH, "layers",
                                        "merge_fill.py"),
                           "bench_layer_merge_fill").read(run)


def _run(at_window, after):
    return {"out": {"artifacts": {"at_window": {"counters": at_window},
                                  "after": {"counters": after}}}}


def test_reads_the_rise_over_the_window():
    # the warm-up's counts are not the window's
    at = {"search.rows_new": 100, "search.slots_merged": 65536,
          "search.seen_slots": 1 << 20}
    after = {"search.rows_new": 100 + 3 * 5000,
             "search.slots_merged": 65536 + 3 * 131072,
             "search.seen_slots": 4 << 20}
    assert _read(_run(at, after)) == 100.0 * 15000 / 393216
    # a counter that first rose inside the window
    assert _read(_run({}, {"search.rows_new": 30,
                           "search.slots_merged": 40})) == 75.0


def test_none_where_there_is_nothing_to_read():
    # the parent's counters; no artifacts; nothing built
    parent = {"search.rows_new": 157, "search.seen_slots": 7168,
              "search.slots_probed": 512}
    assert _read(_run({}, parent)) is None
    assert _read({}) is None and _read({"out": {}}) is None
    assert _read(_run({}, dict(parent, **{"search.slots_merged": 0}))) \
        is None


def test_the_manifest_entry():
    bm = lib.load_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
    # found by name, not by place: later PRs append after it
    (entry,) = [m for m in bm["per_layer"] if m["name"] == "merge_fill"]
    assert entry == {"name": "merge_fill", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "states_per_s", "workloads": list(CELLS)}
    for cell in CELLS:
        assert "merge_fill" in [m["name"]
                                for m in lib.resolve(cell)["per_layer"]]
