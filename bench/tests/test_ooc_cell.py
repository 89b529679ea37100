"""The cell `desk-ooc-4p8` (added in PR 32): the manifest's new entries found
BY NAME, the cell's files, its CPU rehearsal, the plain reference against the
pins, the six new readers on a capped toy session driven through the
`recheck` driver and on a program that has nothing to read, and a broken
count coming out `correct: false`."""

import json
import os
import subprocess
import sys
import time

import lib

CELL, CONFIG, MIX = "desk-ooc-4p8", "desk-ooc-1chip", "recheck-ooc-4p8"
NEW = {"tier_probe_s": ("s/search", "program_span"),
       "tier_spill_s": ("s/search", "program_span"),
       "tier_idle_s": ("s/search", "program_span"),
       "spills_per_search": ("count", "program_counter"),
       "cold_share": ("%", "program_counter"),
       "redone_share": ("%", "program_counter")}
# the accepted metrics whose lists the cell's name was appended to
LISTED = ("build_s.desk", "cache_load_s.desk", "device_init_s.desk",
          "expand_device_s", "sort_device_s", "probe_device_s",
          "scatter_device_s", "compact_device_s", "unscoped_device_share",
          "seed_idle_s", "sync_idle_s", "unattributed_idle_s", "sort_fill",
          "dispatch_idle_s", "seen_fill", "probe_fill", "merge_fill",
          "seed_mb_per_search", "table_mb")
UNLISTED = ("dispatches_per_search", "window_recompiles",
            "search_hbm_roofline", "hbm_peak_mb")
BM = lib.load_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
# 4 procs / MaxMoney 2 under a cap of 2^12 rows: three spills, 67 cold
# duplicates (tests/test_bench_pins.py::_cold_spills has the arithmetic)
TOY_CFG = ("SPECIFICATION Spec\nINVARIANT AliceBounded\nCONSTANTS\n"
           "  Procs = {p1, p2, p3, p4}\n  MaxMoney = 2\n")
TOY_CAP = 1 << 12
TOY = {"generated": 19101, "distinct": 7293, "spills": 3, "redone": 11015,
       "cold_keys": 4679}


def _read(name, run):
    return lib.load_module(os.path.join(lib.BENCH, "layers", name + ".py"),
                           "bench_layer_" + name).read(run)


def test_the_entries_in_the_manifest_by_name():
    conf = {c["name"]: c for c in BM["configs"]}[CONFIG]
    assert conf["file"] == "bench/configs/desk-ooc-1chip.json"
    assert conf["reduced"] == ["MaxMoney", "device_cap", "disk_rung"]
    assert len(conf["source"]) <= 200 and "Yu/Manolios/Lamport 1999" in \
        conf["source"]
    assert conf["source"] == lib.load_json(
        os.path.join(lib.ROOT, conf["file"]))["source"]
    cell = {w["name"]: w for w in BM["workloads"]}[CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": MIX,
                    "chips": 4, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert CELL in e2e["states_per_s"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    by_name = {m["name"]: m for m in BM["per_layer"]}
    for name in LISTED:
        assert CELL in by_name[name]["workloads"], name
    for name, (unit, source) in NEW.items():
        m = by_name[name]
        # this cell, by name; a later capped cell may be appended
        assert m["workloads"][:1] == [CELL]
        assert (m["moves"], m["layer"]) == ("states_per_s", "engines")
        assert (m["unit"], m["source"]) == (unit, source)
        assert lib.NAME_RE.match(name) and lib.UNIT_RE.match(unit)
    # the mesh's metrics are not this cell's, and it brings no roofline
    assert CELL not in by_name["exchange_device_s"]["workloads"]
    assert not [n for n in by_name if n.endswith("_roofline")
                and n not in ("search_hbm_roofline",
                              "exchange_ici_roofline")]
    assert len(BM["workloads"]) >= 6 and len(BM["configs"]) >= 4


def test_the_cell_resolves_to_files_that_exist():
    res = lib.resolve(CELL)
    conf, mix, pins = res["config"], res["mix"], res["pins"]
    assert conf["name"] == CONFIG and conf["chips"] == 1
    assert conf["architecture"] is None
    assert set(conf["reduced"]) == {"MaxMoney", "device_cap", "disk_rung"}
    assert {"scale", "res_caps", "host_tier"} <= set(conf["assumed"])
    one = lib.resolve("desk-recheck-4p8")
    # desk-1chip's guarantees and two of its own
    n = len(one["config"]["guarantees"])
    assert conf["guarantees"][:n] == one["config"]["guarantees"]
    assert len(conf["guarantees"]) == n + 2
    assert "EVERY search" in conf["guarantees"][n]
    assert "tier.cap_breached" in conf["guarantees"][n + 1]
    assert conf["session"] == one["config"]["session"]
    # the same model, reference, driver and seed generator as the in-core
    # cell: the mix differs by the cap and the pins alone
    # `recheck` itself, reached through the whole-host driver (chips: 4)
    assert (mix["driver"], mix["reference"]) == ("recheck-host",
                                                 "transfer_scaled")
    assert mix["session"] == dict(one["mix"]["session"],
                                  seen_cap=pins["seen_cap"])
    for key in ("spec", "cfg", "use_pinned_caps", "state_words",
                "key_words", "trace_searches", "rehearsal_cfg"):
        assert mix[key] == one["mix"][key], key
    for path in (mix["spec"], mix["cfg"]):
        assert os.path.isfile(os.path.join(lib.ROOT, path)), path
    assert os.path.isfile(res["driver_path"])
    for key in ("generated", "distinct", "diameter", "levels"):
        assert pins[key] == one["pins"][key], key
    caps = pins["res_caps"]
    assert caps["SC"] == pins["seen_cap"] == 1 << 20
    assert {k: caps[k] for k in ("FCap", "AccCap", "VC")} == \
        {k: one["pins"]["res_caps"][k] for k in ("FCap", "AccCap", "VC")}
    assert conf["scale"]["device_cap_rows"] == pins["seen_cap"]
    per = conf["scale"]["per_search"]
    tier = pins["tier"]
    assert (per["spills"], per["keys_spilled"], per["candidates_redone"],
            per["keys_probed_on_the_host"], per["cold_duplicates_dropped"],
            per["cold_keys_at_the_end"], per["hot_keys_at_the_end"]) == \
        (len(tier["spills"]), tier["spilled_keys"], tier["redone_rows"],
         tier["keys_probed"], tier["keys_dropped"], tier["cold_keys"],
         tier["hot_keys_at_end"])
    # `cold_share` reads keys IN RUNS over distinct states; a cold duplicate
    # spilled a second time sits in two runs, so the distinct share is lower
    assert per["keys_in_cold_runs_over_distinct_pct"] == round(
        100 * tier["cold_keys"] / pins["distinct"], 2) == 77.71
    assert per["cold_distinct_keys_at_the_end"] == tier["cold_distinct"]
    assert per["cold_distinct_over_distinct_pct"] == round(
        100 * tier["cold_distinct"] / pins["distinct"], 2) == 77.42
    assert [m["name"] for m in res["end_to_end"]] == ["states_per_s",
                                                      "setup_s"]
    names = {m["name"] for m in res["per_layer"]}
    assert names == set(NEW) | set(LISTED) | set(UNLISTED)
    for name in names:
        assert os.path.isfile(res["reader_path"](name)), name


def test_reference_recomputes_the_pins():
    """Seconds in numpy; what every run of the cell does after its window:
    one in-memory set, nothing of jaxmc, nothing of tiers."""
    res = lib.resolve(CELL)
    src = open(os.path.join(lib.ROOT, res["mix"]["cfg"])).read()
    ref = lib.reference_answer(res["mix"], lib.permute_cfg(src, 2 ** 31 + 32))
    lib.check_pins(ref, res["pins"])
    assert ref["levels"] == res["pins"]["levels"] and ref["ok"] is True
    assert (ref["generated"], ref["distinct"], ref["diameter"]) == \
        (4767576, 1859252, 12)
    assert res["config"]["scale"]["widest_level_candidates"] == \
        max(c for _, c, _ in ref["levels"]) <= res["pins"]["seen_cap"]


def _run_py(args):
    return subprocess.run(
        [sys.executable, os.path.join(lib.BENCH, "run.py")] + args,
        cwd=lib.ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", JAXMC_COMPILE_CACHE="off"))


def test_traced_rehearsal_gives_no_result():
    """The harness's own rehearsal (2 procs / MaxMoney 3, the cap far above
    the model): the plumbing, every accepted reader, and a capped session
    that never spills — `cold_share` 0, the other five left out."""
    p = _run_py(["--workload", CELL, "--seed", "2147484032", "--seconds",
                 "1", "--trace", "1", "--rehearse-on-cpu"])
    assert p.returncode == 0, p.stderr[-2000:]
    assert "NOT a chip run" in p.stdout and "correct=True" in p.stdout
    for line in p.stdout.splitlines():
        try:
            assert "correct" not in json.loads(line)
        except ValueError:
            pass
    assert "bench: cold_share = 0.0 %" in p.stdout
    assert "bench: table_mb = " in p.stdout
    for name in set(NEW) - {"cold_share"}:
        assert f"bench: {name} = " not in p.stdout, name


def _toy_ctx(seed, seconds, trace=False):
    """The cell's own files with the toy in the rehearsal's place: the
    driver passes `mix["session"]` to the session as it stands, so a cap of
    2^12 rows on 4 procs / MaxMoney 2 spills on XLA:CPU as the cell does on
    the chip (capacities from the engine's own ladder, not the pins)."""
    res = lib.resolve(CELL)
    mix = dict(res["mix"], rehearsal_cfg=TOY_CFG,
               session=dict(res["mix"]["session"], seen_cap=TOY_CAP))
    # in this process jax is up already, with however many CPU devices: the
    # search needs one
    return dict(res, mix=mix, cell=dict(res["cell"], chips=1), seed=seed,
                seconds=seconds, trace=trace, rehearsal=True, t0=time.time())


def test_the_six_readers_on_a_capped_toy_through_the_driver(monkeypatch):
    monkeypatch.setenv("JAXMC_COMPILE_CACHE", "off")
    monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")
    ctx = _toy_ctx(2147484132, 0.5, trace=True)
    driver = lib.load_module(ctx["driver_path"], "bench_driver_ooc_toy")
    out = driver.run(ctx)
    assert out["correct"] is True and out["failed"] == 0
    art = out["artifacts"]
    assert art["searches"] == 1 and out["trace_dir"]
    assert (art["reference"]["generated"], art["reference"]["distinct"]) \
        == (TOY["generated"], TOY["distinct"])
    assert "tier.cap_breached" not in art["after"]["gauges"]
    run = {"out": out, "trace": None, "mix": ctx["mix"], "pins": ctx["pins"],
           "cell": ctx["cell"], "bench_dir": lib.BENCH}
    assert _read("spills_per_search", run) == TOY["spills"]
    assert _read("cold_share", run) == \
        100.0 * TOY["cold_keys"] / TOY["distinct"]
    assert _read("redone_share", run) == \
        100.0 * TOY["redone"] / TOY["generated"]
    probe, spill = _read("tier_probe_s", run), _read("tier_spill_s", run)
    assert 0 < spill < probe < 60
    # the trace is XLA:CPU's: it has the program's host spans and no
    # device line, so no idle gap to hand out (0, as `sync_idle_s` reads in
    # every rehearsal); and without a trace there is nothing to read
    assert _read("tier_idle_s", run) == 0.0
    out["trace_dir"] = None
    assert _read("tier_idle_s", run) is None


def _bare_run(counters=None, gauges=None, phases=None, searches=2):
    res = lib.resolve(CELL)
    a, b = counters or ({}, {})
    pa, pb = phases or ({}, {})
    out = {"trace_dir": None, "device": {"kind": "TPU v5 lite"},
           "artifacts": {"searches": searches,
                         "reference": {"generated": 4767576,
                                       "distinct": 1859252},
                         "at_window": {"counters": a, "gauges": {},
                                       "phases": pa},
                         "after": {"counters": b, "gauges": gauges or {},
                                   "phases": pb}}}
    return {"out": out, "trace": None, "mix": res["mix"],
            "pins": res["pins"], "cell": res["cell"],
            "bench_dir": lib.BENCH}


def test_the_readers_by_hand_at_the_cells_size():
    """One warm-up search before the window and two inside it, the pins'
    arithmetic in the counters."""
    tier = lib.resolve(CELL)["pins"]["tier"]
    one = {"tier.spills": 4, "tier.redone_rows": tier["redone_rows"]}
    run = _bare_run((one, {k: 3 * v for k, v in one.items()}),
                    {"tier.occupancy": {"device": 424296, "host": tier["cold_keys"],
                                        "disk": 0}},
                    ({"tier.spill": 0.5, "tier.pull": 0.1, "tier.keys": 0.2,
                      "tier.probe": 1.0, "tier.push": 0.1, "search": 9.0},
                     {"tier.spill": 1.5, "tier.pull": 0.3, "tier.keys": 0.6,
                      "tier.probe": 3.0, "tier.push": 0.3, "search": 27.0}))
    assert _read("spills_per_search", run) == 4
    assert round(_read("redone_share", run), 4) == 70.6325
    assert round(_read("cold_share", run), 4) == 77.7091
    assert round(_read("tier_spill_s", run), 9) == 0.5
    assert round(_read("tier_probe_s", run), 9) == 1.4


def test_nothing_to_read_is_none():
    """The program as the parent has it (no such span, counter or gauge),
    an uncapped run, and no run at all."""
    bare = _bare_run(({"search.rows_new": 1}, {"search.rows_new": 2}),
                     {"profile.status": "learned"},
                     ({"search": 1.0}, {"search": 3.0}))
    for name in NEW:
        assert _read(name, bare) is None, name
        assert _read(name, {"out": None}) is None, name
    assert _read("spills_per_search", _bare_run(
        ({}, {"tier.spills": 8}), searches=0)) is None


def test_a_broken_count_comes_out_not_correct(monkeypatch, capsys):
    """The recheck driver on the capped toy with one distinct state lost
    from every search after the warm-up (what a probe that finds a key in a
    run it should not hold would do)."""
    monkeypatch.setenv("JAXMC_COMPILE_CACHE", "off")
    monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")
    sys.path.insert(0, lib.ROOT)
    from jaxmc.session import CheckSession
    real = CheckSession.explore
    calls = {"n": 0}

    def lossy(self, *a, **kw):
        res = real(self, *a, **kw)
        calls["n"] += 1
        if calls["n"] >= 2:
            res.distinct -= 1
        return res

    monkeypatch.setattr(CheckSession, "explore", lossy)
    ctx = _toy_ctx(32, 0.5)
    driver = lib.load_module(ctx["driver_path"], "bench_driver_ooc_broken")
    out = driver.run(ctx)
    assert out["failed"] == out["attempted"] >= 1
    assert out["correct"] is False
    assert "FAILED" in capsys.readouterr().out
