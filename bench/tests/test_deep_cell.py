"""The cell `desk-deep-4p` (added in PR 30): the manifest's new entries
found BY NAME, the cell's files, its CPU rehearsal, the plain reference
against the pins at the full rung, the two new readers, and a broken count
coming out `correct: false`."""

import json
import os
import subprocess
import sys
import time

import lib

CELL, CONFIG, MIX = "desk-deep-4p", "desk-deep-1chip", "recheck-deep-4p"
NEW = ("seed_mb_per_search", "table_mb")
# the accepted metrics whose lists the cell's name was appended to
LISTED = ("build_s.desk", "cache_load_s.desk", "device_init_s.desk",
          "expand_device_s", "sort_device_s", "probe_device_s",
          "scatter_device_s", "compact_device_s", "unscoped_device_share",
          "seed_idle_s", "sync_idle_s", "unattributed_idle_s", "sort_fill",
          "dispatch_idle_s", "seen_fill", "probe_fill", "merge_fill")
UNLISTED = ("dispatches_per_search", "window_recompiles",
            "search_hbm_roofline", "hbm_peak_mb")
BM = lib.load_json(os.path.join(lib.ROOT, "BENCHMARK.json"))


def _read(name, run):
    return lib.load_module(os.path.join(lib.BENCH, "layers", name + ".py"),
                           "bench_layer_" + name).read(run)


def test_the_entries_in_the_manifest_by_name():
    conf = {c["name"]: c for c in BM["configs"]}[CONFIG]
    assert conf["file"] == "bench/configs/desk-deep-1chip.json"
    assert conf["reduced"] == [] and len(conf["source"]) <= 200
    assert "transfer_scaled_4p.cfg" in conf["source"]
    assert conf["source"] == lib.load_json(
        os.path.join(lib.ROOT, conf["file"]))["source"]
    cell = {w["name"]: w for w in BM["workloads"]}[CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": MIX,
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert CELL in e2e["states_per_s"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    cells = [w["name"] for w in BM["workloads"]]
    by_name = {m["name"]: m for m in BM["per_layer"]}
    for name in LISTED:
        assert CELL in by_name[name]["workloads"], name
    for name in NEW:
        m = by_name[name]
        # the five cells of PR 30, by name; a later cell may be appended
        assert m["workloads"][:5] == cells[:5] and CELL in cells[:5]
        assert m["moves"] == "states_per_s"
        assert (m["layer"], m["source"]) == ("engines", "program_counter")
        assert lib.NAME_RE.match(name) and lib.UNIT_RE.match(m["unit"])
    # no roofline of its own: no new kernel
    assert not [n for n in by_name if n.endswith("_roofline")
                and n not in ("search_hbm_roofline",
                              "exchange_ici_roofline")]


def test_the_cell_resolves_to_files_that_exist():
    res = lib.resolve(CELL)
    assert res["config"]["name"] == CONFIG and res["config"]["chips"] == 1
    assert res["config"]["reduced"] == {}
    assert res["config"]["architecture"] is None
    # the scale is the repo's own choice and is listed as such; the pins
    # are what a cold run leaves (tests/test_bench_pins.py is that run)
    assert {"scale", "res_caps"} <= set(res["config"]["assumed"])
    one = lib.resolve("desk-recheck-4p8")
    assert res["config"]["guarantees"] == one["config"]["guarantees"]
    assert res["config"]["session"] == one["config"]["session"]
    mix = res["mix"]
    assert (mix["driver"], mix["reference"]) == ("recheck", "transfer_scaled")
    assert mix["session"] == {"resident": True, "no_trace": True}
    assert mix["use_pinned_caps"] is True and mix["trace_searches"] == 1
    assert mix["rehearsal_cfg"] == one["mix"]["rehearsal_cfg"]
    for path in (mix["spec"], mix["cfg"]):
        assert os.path.isfile(os.path.join(lib.ROOT, path)), path
    assert os.path.isfile(res["driver_path"])
    assert open(os.path.join(lib.ROOT, mix["cfg"])).read() == \
        open(os.path.join(lib.ROOT, "specs/transfer_scaled_4p.cfg")).read()
    assert [m["name"] for m in res["end_to_end"]] == ["states_per_s",
                                                      "setup_s"]
    names = {m["name"] for m in res["per_layer"]}
    assert names == set(NEW) | set(LISTED) | set(UNLISTED)
    for name in names:
        assert os.path.isfile(res["reader_path"](name)), name
    table = res["config"]["scale"]["table_bytes"]
    caps = res["pins"]["res_caps"]
    assert table["the resident program's capacity-sized tables, the three above "
                 "(search.table_bytes)"] == 4 * (
        caps["SC"] * 5 + caps["FCap"] * 2 + caps["AccCap"] * 7)
    assert table["host-built and uploaded at the start of every search "
                 "(search.seed_bytes)"] == 4 * (caps["SC"] * 5
                                                + caps["FCap"] * 2)


def test_reference_recomputes_the_pins_at_the_full_rung():
    """Seconds in numpy; what every run of the cell does after its window."""
    res = lib.resolve(CELL)
    pin = res["pins"]
    src = open(os.path.join(lib.ROOT, res["mix"]["cfg"])).read()
    ref = lib.reference_answer(res["mix"], lib.permute_cfg(src, 2 ** 31 + 30))
    lib.check_pins(ref, pin)
    assert ref["levels"] == pin["levels"] and ref["ok"] is True
    assert (ref["generated"], ref["distinct"], ref["diameter"]) == \
        (24035597, 9394019, 12)
    assert res["config"]["scale"]["generated"] == ref["generated"]
    assert res["config"]["scale"]["distinct"] == ref["distinct"]
    assert res["config"]["scale"]["largest_frontier"] == \
        max(f for f, _, _ in ref["levels"])
    assert res["config"]["scale"]["widest_level_candidates"] == \
        max(c for _, c, _ in ref["levels"])


def _run_py(args):
    return subprocess.run(
        [sys.executable, os.path.join(lib.BENCH, "run.py")] + args,
        cwd=lib.ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", JAXMC_COMPILE_CACHE="off"))


def test_traced_rehearsal_reads_the_two_counters_and_gives_no_result():
    """(`test_rehearse.py` rehearses every cell of the manifest, this one
    too; here: the program's two new counters reach their readers.)"""
    p = _run_py(["--workload", CELL, "--seed", "2147483999", "--seconds",
                 "1", "--trace", "1", "--rehearse-on-cpu"])
    assert p.returncode == 0, p.stderr[-2000:]
    assert "NOT a chip run" in p.stdout and "correct=True" in p.stdout
    for line in p.stdout.splitlines():
        try:
            assert "correct" not in json.loads(line)
        except ValueError:
            pass
    assert "bench: seed_mb_per_search = " in p.stdout
    assert "bench: table_mb = " in p.stdout


def _run(counters=None, gauges=None, searches=2):
    res = lib.resolve(CELL)
    a, b = counters or ({}, {})
    out = {"trace_dir": None, "device": {"kind": "TPU v5 lite"},
           "artifacts": {"searches": searches,
                         "at_window": {"counters": a, "gauges": {}},
                         "after": {"counters": b, "gauges": gauges or {}}}}
    return {"out": out, "trace": None, "mix": res["mix"],
            "pins": res["pins"], "cell": res["cell"],
            "bench_dir": lib.BENCH}


def test_the_two_readers_on_a_hand_made_run():
    """The deep pins' capacities, K = 5 key words and PW = 2 row words:
    one warm-up search before the window and two searches inside it."""
    caps = lib.resolve(CELL)["pins"]["res_caps"]
    seed = 4 * (caps["SC"] * 5 + caps["FCap"] * 2)
    table = seed + 4 * caps["AccCap"] * 7
    run = _run(({"search.seed_bytes": seed}, {"search.seed_bytes": 3 * seed}),
               {"search.table_bytes": table})
    assert _read("seed_mb_per_search", run) == 369.098752
    assert _read("table_mb", run) == 603.979776


def test_nothing_to_read_is_none_never_zero():
    """The program as the parent has it: no such counter, no such gauge."""
    bare = _run(({"search.rows_new": 1}, {"search.rows_new": 2}),
                {"profile.status": "learned"})
    for name in NEW:
        assert _read(name, bare) is None, name
    assert _read("seed_mb_per_search", _run(
        ({}, {"search.seed_bytes": 8}), searches=0)) is None
    assert _read("table_mb", {"out": None}) is None


def test_a_broken_count_comes_out_not_correct(monkeypatch, capsys):
    """The recheck driver on this cell's files (toy size: the rehearsal's
    cfg) with one generated state lost from the second search on."""
    monkeypatch.setenv("JAXMC_COMPILE_CACHE", "off")
    sys.path.insert(0, lib.ROOT)
    from jaxmc.session import CheckSession
    real = CheckSession.explore
    calls = {"n": 0}

    def lossy(self, *a, **kw):
        res = real(self, *a, **kw)
        calls["n"] += 1
        if calls["n"] >= 3:
            res.generated -= 1
        return res

    monkeypatch.setattr(CheckSession, "explore", lossy)
    res = lib.resolve(CELL)
    driver = lib.load_module(res["driver_path"], "bench_driver_deep_broken")
    out = driver.run(dict(res, seed=30, seconds=1.5, trace=False,
                          rehearsal=True, t0=time.time()))
    assert out["attempted"] >= 3
    assert out["failed"] == out["attempted"] - 1
    assert out["correct"] is False
    assert "FAILED" in capsys.readouterr().out
