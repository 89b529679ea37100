"""The four-chip cell `mesh-recheck-4p` (added in PR 26): its pins against
the plain reference, its mesh capacities against the reference's levels,
its entries in the manifest, the control on its pins, and its readers —
`None`, never 0, where there is nothing to read."""

import os

import pytest

import control
import lib
import reduce as R

CELL = "mesh-recheck-4p"
NEW = ("exchange_device_s", "exchange_ici_roofline", "supersteps_per_search",
       "exchange_mb_per_search", "shard_balance", "devices_busy")
# PR 24's twelve: the mesh engine runs those layers under the same scopes,
# spans and counters, so the cell is on their lists too
SHARED = ("expand_device_s", "sort_device_s", "probe_device_s",
          "scatter_device_s", "compact_device_s", "unscoped_device_share",
          "seed_idle_s", "sync_idle_s", "unattributed_idle_s", "sort_fill",
          "dispatch_idle_s", "seen_fill")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "small_tpu_scoped.xplane.pb")
REF = lib.load_module(os.path.join(lib.BENCH, "reference",
                                   "transfer_scaled.py"), "ref_mesh_cell")


def _read(name, run):
    return lib.load_module(os.path.join(lib.BENCH, "layers", name + ".py"),
                           "bench_layer_" + name).read(run)


def test_reference_recomputes_the_pins_and_the_mesh_caps_hold():
    pin = lib.resolve(CELL)["pins"]
    cfg = open(os.path.join(lib.ROOT, pin["cfg"])).read()
    n, m, invs = REF.parse_cfg(cfg)
    assert (n, m, invs) == (pin["procs"], pin["max_money"],
                            ["AliceBounded"])
    got = REF.explore(n, m)
    for key in ("generated", "distinct", "diameter", "levels"):
        assert got[key] == pin[key], key
    assert got["ok"] is True
    assert (got["generated"], got["distinct"]) == (4767576, 1859252)
    # ... the counts of its one-chip pair
    assert {k: pin[k] for k in ("generated", "distinct", "levels")} == \
        {k: v for k, v in lib.resolve("desk-recheck-4p8")["pins"].items()
         if k in ("generated", "distinct", "levels")}
    # per shard, at the balance the cell may show (<= 1.05 of the mean)
    caps, lv, d, skew = pin["res_caps"], pin["levels"], 4, 1.05
    seen = 0
    for frontier, generated, new in lv:
        seen += frontier
        assert frontier * skew / d <= caps["FC"]
        assert generated * skew / d <= caps["VC"]
    assert seen == pin["distinct"] and seen * skew / d <= caps["SC"]
    assert len(lv) <= caps["TRL"] and len(lv) <= caps["MSL"]
    assert caps["VC"] >= caps["FC"]
    # what the buckets hold at gamma: a shard's candidates of a level fit
    # the bucket of one peer D times over
    import shapes_mesh as M
    b, sb = M.buckets(pin["expand_instances"], caps["FC"], caps["GAM16"], d)
    assert max(g for _, g, _ in lv) * skew / d / d <= b + sb


def test_the_entries_in_the_manifest():
    bm = lib.load_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
    res = lib.resolve(CELL)
    assert res["cell"]["chips"] == res["config"]["chips"] == 4
    assert res["config"]["session"]["devices"] == 4
    assert res["mix"]["driver"] == "recheck"
    assert res["mix"]["session"]["devices"] == 4
    assert [m["name"] for m in res["end_to_end"]] == ["states_per_s",
                                                      "setup_s"]
    tail = bm["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == list(NEW)
    for m in tail:
        assert m["workloads"] == [CELL] and m["moves"] == "states_per_s"
        assert os.path.isfile(res["reader_path"](m["name"]))
        assert m["layer"] in ("kernels", "engines", "device")
    names = {m["name"] for m in res["per_layer"]}
    # the six, PR 24's twelve, the four accepted metrics that list no cells
    assert names == set(NEW) | set(SHARED) | {
        "dispatches_per_search", "window_recompiles", "search_hbm_roofline",
        "hbm_peak_mb"}
    # what test_trace_spans.py asks of the twelve, but for their PLACE in
    # the list (new entries go to the end): every cell is on every list,
    # and nothing but the cell's name was added to an accepted entry
    cells = [w["name"] for w in bm["workloads"]]
    by_name = {m["name"]: m for m in bm["per_layer"]}
    for name in SHARED:
        assert by_name[name]["workloads"] == cells, name
        assert os.path.isfile(res["reader_path"](name))
    for w in cells:
        got = {m["name"] for m in lib.resolve(w)["per_layer"]}
        assert got >= set(SHARED) and (w == CELL or not got & set(NEW))
    four = [w for w in bm["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bm["workloads"]) // 2)
    assert res["config"]["reduced"].keys() == {"MaxMoney"}
    assert any("fewer devices gives no result" in g
               for g in res["config"]["guarantees"])


def test_the_control_comes_out_not_correct_on_the_new_pins(capsys):
    """At the control's own width (4 bits) and at one bit, as PERF.md §2
    has it for the one-chip pair of this cell."""
    assert control.main(["--workload", CELL, "--seeds", "5"]) == 0
    assert control.main(["--workload", CELL, "--seeds", "6",
                         "--drop-bits", "1"]) == 0
    out = capsys.readouterr().out
    assert out.count("correct=False") == 2 and "FAILED" in out


def _run(tmp_path, trace_path=None, counters=None, gauges=None):
    res = lib.resolve(CELL)
    out = {"trace_dir": None, "device": {"kind": "TPU v5 lite"},
           "artifacts": {"searches": 2,
                         "at_window": {"counters": (counters or ({}, {}))[0],
                                       "gauges": {}},
                         "after": {"counters": (counters or ({}, {}))[1],
                                   "gauges": gauges or {}}}}
    trace = None
    if trace_path:
        d = tmp_path / "plugins" / "profile" / "t"
        d.mkdir(parents=True, exist_ok=True)
        (d / "t.xplane.pb").write_bytes(open(trace_path, "rb").read())
        out["trace_dir"] = str(tmp_path)
        trace = R.reduce_trace(trace_path)
    return {"out": out, "trace": trace, "mix": res["mix"],
            "pins": res["pins"], "cell": res["cell"],
            "bench_dir": lib.BENCH}


def test_nothing_to_read_is_none_never_zero(tmp_path):
    """The program as the parent has it — no `mesh.*` counter, no gauge,
    no trace — and a one-chip trace with none of the mesh's scopes."""
    bare = _run(tmp_path)
    for name in NEW:
        assert _read(name, bare) is None, name
    one_chip = _run(tmp_path, SCOPED)
    for name in ("exchange_device_s", "exchange_ici_roofline"):
        assert _read(name, one_chip) is None, name


def test_the_readers_read(tmp_path):
    run = _run(tmp_path, SCOPED,
               ({"mesh.host_syncs": 4, "mesh.exchange_bytes": 10 ** 9},
                {"mesh.host_syncs": 6, "mesh.exchange_bytes": 5 * 10 ** 9}),
               {"mesh.shard_balance": 1.0007})
    assert _read("supersteps_per_search", run) == 1.0
    assert _read("exchange_mb_per_search", run) == 2000.0
    assert _read("shard_balance", run) == 1.0007
    assert _read("devices_busy", run) == 1      # the recorded one-chip trace


def test_the_exchange_readers_on_a_trace_that_names_the_exchange(
        tmp_path, monkeypatch):
    """The recorded trace's sort, re-labelled as the exchange: the seconds
    are the scope's, and the share is the shapes' bytes over them."""
    import shapes_mesh as M
    import spans as S
    real = S.scope_of
    monkeypatch.setattr(
        S, "scope_of", lambda tf_op: {"jaxmc.merge.sort": "jaxmc.mesh.exchange",
                                      "jaxmc.expand": "jaxmc.mesh.route"}
        .get(real(tf_op), real(tf_op)))
    S.analyze.cache_clear()
    try:
        run = _run(tmp_path, SCOPED)
        an = S.analyze(SCOPED)
        link = an["scope_s"]["jaxmc.mesh.exchange"] / an["searches"]
        secs = link + an["scope_s"]["jaxmc.mesh.route"] / an["searches"]
        assert _read("exchange_device_s", run) == pytest.approx(secs)
        # the share: rows that exist over the COLLECTIVES' seconds alone;
        # neither the buckets' padding nor the route's scatters are in it
        routed = 4767576 / 4 * 3 / 4 * 32
        assert M.routed_of_run(run) == routed
        want = 100.0 * routed / 200e9 / link
        assert _read("exchange_ici_roofline", run) == pytest.approx(want)
    finally:
        S.analyze.cache_clear()


def test_the_fill_readers_read_the_mesh_engines_counters(tmp_path):
    """`sort_fill` / `seen_fill` list the mesh cell too: the mesh engine
    writes the counters they read, summed over the shards."""
    run = _run(tmp_path, None,
               ({}, {"search.rows_valid": 247, "search.slots_sorted": 7168,
                     "search.rows_new": 157, "search.seen_slots": 28672}))
    assert _read("sort_fill", run) == pytest.approx(100 * 247 / 7168)
    assert _read("seen_fill", run) == pytest.approx(100 * 157 / 28672)
