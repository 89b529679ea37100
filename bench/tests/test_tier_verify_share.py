"""`tier_verify_share` (added in PR 50): the reader on hand-made counters,
the rise over the window and not the warm-up's counts, `None` where the
program has no `tier.keys_verified`, and its manifest entry."""

import os

import lib

CELLS = ("desk-ooc-4p8",)


def _read(run):
    return lib.load_module(os.path.join(lib.BENCH, "layers",
                                        "tier_verify_share.py"),
                           "bench_layer_tier_verify_share").read(run)


def _run(at_window, after):
    return {"out": {"artifacts": {"at_window": {"counters": at_window},
                                  "after": {"counters": after}}}}


def test_reads_the_rise_over_the_window():
    # the warm-up's counts are not the window's: a search probes 1,470,128
    # keys and 10,820 of them pass a fence; a warm-up that read otherwise
    # (a run on disk: every key verified) must not show
    at = {"tier.keys_probed": 1470128, "tier.keys_verified": 1470128,
          "tier.keys_dropped": 9852}
    after = {"tier.keys_probed": 16 * 1470128,
             "tier.keys_verified": 1470128 + 15 * 10820,
             "tier.keys_dropped": 16 * 9852}
    assert _read(_run(at, after)) == 100.0 * 10820 / 1470128
    # counters that first rose inside the window; every query past every
    # fence of two runs
    assert _read(_run({}, {"tier.keys_probed": 40,
                           "tier.keys_verified": 80})) == 200.0


def test_none_where_there_is_nothing_to_read():
    # the parent's counters; no artifacts; a window that probed nothing
    parent = {"tier.keys_probed": 1470128, "tier.keys_dropped": 9852,
              "tier.spills": 4}
    assert _read(_run({}, parent)) is None
    assert _read(_run(parent, {k: 2 * v for k, v in parent.items()})) is None
    assert _read({}) is None and _read({"out": {}}) is None
    assert _read(_run({}, {"tier.keys_probed": 0,
                           "tier.keys_verified": 0})) is None
    uncapped = {"search.rows_new": 166, "search.slots_merged": 32768}
    assert _read(_run(uncapped, uncapped)) is None


def test_the_manifest_entry():
    bm = lib.load_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
    # found by name, not by place: later PRs append after it
    (entry,) = [m for m in bm["per_layer"]
                if m["name"] == "tier_verify_share"]
    assert entry["workloads"][:len(CELLS)] == list(CELLS)
    assert dict(entry, workloads=None) == {
        "name": "tier_verify_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "engines",
        "moves": "states_per_s", "workloads": None}
    for w in bm["workloads"]:
        names = [m["name"] for m in lib.resolve(w["name"])["per_layer"]]
        assert ("tier_verify_share" in names) == \
            (w["name"] in entry["workloads"])
