"""The cell `desk-symmetry-5p` (added in PR 47): the manifest's new entries
found BY NAME (lists compared with `>=`, so a later cell may be appended),
the cell's files, the plain reference against the pins, the orbit sum and
the unreduced reference, the three new readers on a hand-made run and on the
parent's (nothing to read: None), the control of `correct` coming out not
correct, the CPU rehearsal ending without a result object, a driver fed a
miscounted search coming out `correct: false`, and the guard: an engine that
does not apply the SYMMETRY on the device ends the run before any search."""

import json
import os
import subprocess
import sys

import pytest

import lib

CELL, CONFIG, MIX = ("desk-symmetry-5p", "desk-symmetry-1chip",
                     "recheck-symmetry-5p")
NEW = ("canon_device_s", "canon_rows_per_search", "canon_hbm_roofline")
BM = lib.load_json(os.path.join(lib.ROOT, "BENCHMARK.json"))


def _load(kind, name):
    return lib.load_module(os.path.join(lib.BENCH, kind, name + ".py"),
                           f"bench_{kind}_{name}")


def test_the_entries_in_the_manifest_by_name():
    conf = {c["name"]: c for c in BM["configs"]}[CONFIG]
    assert conf["file"] == "bench/configs/desk-symmetry-1chip.json"
    assert conf["reduced"] == [] and len(conf["source"]) <= 200
    assert "TLC.tla:13-14" in conf["source"]
    assert conf["source"] == lib.load_json(
        os.path.join(lib.ROOT, conf["file"]))["source"]
    cell = {w["name"]: w for w in BM["workloads"]}[CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": MIX,
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert CELL in e2e["states_per_s"]["workloads"]
    by_name = {m["name"]: m for m in BM["per_layer"]}
    # every accepted metric that lists desk-deep-4p (the same engine in
    # the same capacity class, no_trace) lists this cell too
    deep = {n for n, m in by_name.items()
            if "desk-deep-4p" in m.get("workloads", ())}
    assert len(deep) >= 28
    for name in deep:
        assert CELL in by_name[name]["workloads"], name
    for name in NEW:
        m = by_name[name]
        assert set(m["workloads"]) >= {CELL} and m["moves"] == "states_per_s"
        assert lib.NAME_RE.match(name) and lib.UNIT_RE.match(m["unit"])
    assert by_name["canon_hbm_roofline"]["unit"] == "%"
    names = [m["name"] for m in BM["per_layer"]]
    assert names.index("compact_fill") < min(names.index(n) for n in NEW)
    assert [w["name"] for w in BM["workloads"]].index(CELL) > \
        [w["name"] for w in BM["workloads"]].index("desk-violation-4p")
    four = sum(1 for w in BM["workloads"] if w["chips"] == 4)
    assert four <= len(BM["workloads"]) // 2


def test_the_cell_resolves_to_files_that_exist():
    res = lib.resolve(CELL)
    conf, mix, pins = res["config"], res["mix"], res["pins"]
    assert conf["name"] == CONFIG and conf["chips"] == 1
    assert conf["reduced"] == {} and conf["architecture"] is None
    assert {"Procs", "MaxMoney", "res_caps"} <= set(conf["assumed"])
    deep = lib.resolve("desk-deep-4p")
    assert conf["session"] == deep["config"]["session"]
    assert mix["session"] == deep["mix"]["session"] == {
        "resident": True, "no_trace": True}
    assert (mix["driver"], mix["reference"], mix["symmetry_form"]) == \
        ("symmetry", "transfer_symmetry", "sorted")
    assert mix["use_pinned_caps"] is True and mix["trace_searches"] == 1
    for path in (mix["spec"], mix["cfg"]):
        assert os.path.isfile(os.path.join(lib.ROOT, path)), path
    # the module EXTENDS the spec every other cell checks, in its
    # directory, and adds the permutation set and nothing else
    spec = open(os.path.join(lib.ROOT, mix["spec"])).read()
    body = [ln for ln in spec.splitlines()
            if ln.strip() and not ln.startswith(("\\*", "---", "==="))]
    assert body == ["EXTENDS transfer_scaled, TLC",
                    "Perms == Permutations(Procs)"]
    assert os.path.dirname(mix["spec"]) == \
        os.path.dirname(deep["mix"]["spec"])
    cfg = open(os.path.join(lib.ROOT, mix["cfg"])).read()
    assert cfg.split() == (
        "SPECIFICATION Spec INVARIANT AliceBounded SYMMETRY Perms CONSTANTS "
        "Procs = {p1, p2, p3, p4, p5} MaxMoney = 12").split()
    # the seed permutes the cfg and leaves the SYMMETRY line as it stands
    assert "SYMMETRY Perms\n" in lib.permute_cfg(cfg, 2 ** 31 + 47)
    # tier-1 reads copies under specs/
    for name in ("transfer_symmetry.tla", "transfer_symmetry_5p.cfg"):
        assert open(os.path.join(lib.ROOT, "specs", name)).read() == \
            open(os.path.join(lib.BENCH, "specs", name)).read()
    assert os.path.isfile(res["driver_path"])
    assert [m["name"] for m in res["end_to_end"]] == ["states_per_s",
                                                      "setup_s"]
    names = {m["name"] for m in res["per_layer"]}
    assert names >= set(NEW) | {"dispatches_per_search", "hbm_peak_mb",
                                "program_hbm_mb", "expand_device_s"}
    for name in names:
        assert os.path.isfile(res["reader_path"](name)), name
    # desk-deep-4p's capacity class, a smaller frontier
    caps, deep_caps = pins["res_caps"], deep["pins"]["res_caps"]
    assert {k: caps[k] for k in ("SC", "AccCap", "VC")} == \
        {k: deep_caps[k] for k in ("SC", "AccCap", "VC")}
    assert caps["FCap"] == 1 << 20 < deep_caps["FCap"]
    scale = conf["scale"]
    assert scale["table_bytes"][
        "the resident program's capacity-sized tables, the three above "
        "(search.table_bytes)"] == 4 * (
        caps["SC"] * 5 + caps["FCap"] * 2 + caps["AccCap"] * 7)
    for key in ("generated", "distinct", "unreduced_distinct"):
        assert scale[key] == pins[key], key
    assert scale["levels"] == len(pins["levels"]) == pins["diameter"] + 1
    assert scale["largest_frontier"] == max(
        max(f, n) for f, _, n in pins["levels"]) <= caps["FCap"]
    assert scale["group_order"] == pins["group_order"] == 120
    assert mix["row_lanes"] == 2 + 2 * pins["procs"]


def test_reference_against_the_pins_and_the_unreduced_reference():
    res = lib.resolve(CELL)
    ref_mod = _load("reference", "transfer_symmetry")
    plain = _load("reference", "transfer_scaled")
    # the orbit sum is the unreduced model, wherever both run
    for (n, m), want in (((2, 3), (144, 90, 6)), ((3, 4), (2369, 1148, 9)),
                         ((4, 3), (6160, 2389, 12)),
                         ((3, 12), (56463, 27188, 9))):
        got = ref_mod.explore(n, m)
        assert (got["generated"], got["distinct"], got["diameter"]) == want
        assert got["ok"] is True
        assert ref_mod.unreduced_distinct(n, m) == \
            plain.explore(n, m)["distinct"]
    assert ref_mod.unreduced_distinct(3, 12) == 153701
    # the full rung, on a permuted cfg as a run does (~20 s in numpy)
    src = open(os.path.join(lib.ROOT, res["mix"]["cfg"])).read()
    text = lib.permute_cfg(src, 2 ** 31 + 47)
    assert ref_mod.parse_cfg(text) == (5, 12, ["AliceBounded"])
    ref = lib.reference_answer(res["mix"], text)
    pins = res["pins"]
    lib.check_pins(ref, pins)
    assert ref["levels"] == pins["levels"] and ref["ok"] is True
    assert (ref["generated"], ref["distinct"], ref["diameter"]) == \
        (18403898, 5850788, 15)
    assert ref["generated"] - sum(c for _, c, _ in ref["levels"]) == \
        pins["initial_generated"] == 12 ** 5
    with pytest.raises(ValueError, match="SYMMETRY"):
        ref_mod.parse_cfg(text.replace("SYMMETRY Perms\n", ""))


def test_the_reference_imports_nothing_of_jaxmc():
    src = open(os.path.join(lib.BENCH, "reference",
                            "transfer_symmetry.py")).read()
    imports = [ln for ln in src.splitlines()
               if ln.startswith(("import ", "from "))]
    assert sorted(imports) == ["from __future__ import annotations",
                               "import math", "import numpy as np",
                               "import re"]
    assert "canon" not in src.replace("canonicaliser of the program", "")


def test_the_control_comes_out_not_correct():
    """bench/control.py on this cell: the reference with its dedup key
    narrowed, through the harness's own comparison."""
    control = lib.load_module(os.path.join(lib.BENCH, "control.py"),
                              "bench_control")
    res = lib.resolve(CELL)
    toy = res["mix"]["rehearsal_cfg"]
    ref = lib.reference_answer(res["mix"], toy)
    got = control.control_answer(res["mix"], toy, 4)
    got["truncated"] = False
    assert ref["ok"] is True and (got["distinct"] < ref["distinct"]
                                  or got["generated"] < ref["generated"])
    assert lib.compare(got, ref, "control") is False


def _run(counters=None, searches=2):
    res = lib.resolve(CELL)
    a, b = counters or ({}, {})
    out = {"trace_dir": None, "device": {"kind": "TPU v5 lite"},
           "artifacts": {"searches": searches,
                         "at_window": {"counters": a, "gauges": {}},
                         "after": {"counters": b, "gauges": {}}}}
    return {"out": out, "trace": None, "mix": res["mix"],
            "pins": res["pins"], "cell": res["cell"],
            "bench_dir": lib.BENCH}


def test_the_readers_by_hand_and_on_the_parent(monkeypatch):
    pins = lib.resolve(CELL)["pins"]
    before = {"search.canon_rows": pins["generated"]}        # the warm-up
    after = {"search.canon_rows": 3 * pins["generated"]}
    run = _run((before, after))
    assert _load("layers", "canon_rows_per_search").read(run) == \
        float(pins["generated"])
    # the parent has no such counter and no such scope: nothing to read,
    # the metrics are left out
    for name in NEW:
        assert _load("layers", name).read(_run()) is None, name
    assert _load("layers", "canon_hbm_roofline").read(run) is None
    shapes = lib.load_module(os.path.join(lib.BENCH, "shapes_symmetry.py"),
                             "bench_shapes_symmetry")
    assert shapes.canon_bytes(1000, 12) == 2 * 1000 * 12 * 4
    assert shapes.canon_bytes(0, 12) == 0
    import spans
    # two traced searches whose canonicaliser took 0.04 device seconds
    monkeypatch.setattr(spans, "of_run", lambda r: {
        "searches": 2, "scoped": True, "named": True,
        "scope_s": {"jaxmc.canon": 0.04, "jaxmc.keys": 0.1}, "idle_s": {}})
    assert _load("layers", "canon_device_s").read(run) == 0.02
    # the device's rows: the successors; the initial states went through
    # the function on the host, at the build
    rows = 2 * (pins["generated"] - pins["initial_generated"])
    share = _load("layers", "canon_hbm_roofline").read(run)
    assert share == pytest.approx(
        100 * (shapes.canon_bytes(rows, 12) / 819e9) / 0.04)
    assert 0 < share < 100
    # a trace of the program before PR 47, or a network XLA fused into
    # the key fusion: scoped, but not this scope — None, never a guess
    monkeypatch.setattr(spans, "of_run", lambda r: {
        "searches": 2, "scoped": True, "named": True,
        "scope_s": {"jaxmc.keys": 0.1}, "idle_s": {}})
    for name in ("canon_device_s", "canon_hbm_roofline"):
        assert _load("layers", name).read(run) is None, name


def _run_py(args):
    return subprocess.run(
        [sys.executable, os.path.join(lib.BENCH, "run.py")] + args,
        cwd=lib.ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", JAXMC_COMPILE_CACHE="off"))


def test_traced_rehearsal_reads_the_counter_and_gives_no_result():
    p = _run_py(["--workload", CELL, "--seed", "2147483999", "--seconds",
                 "1", "--trace", "1", "--rehearse-on-cpu"])
    assert p.returncode == 0, p.stderr[-2000:]
    assert "NOT a chip run" in p.stdout and "correct=True" in p.stdout
    for line in p.stdout.splitlines():
        try:
            assert "correct" not in json.loads(line)
        except ValueError:
            pass
    assert "group order 6, form sorted" in p.stdout
    assert "bench: canon_rows_per_search = 1060.0 rows" in p.stdout
    assert "bench: table_mb = " in p.stdout


def test_a_miscounted_search_comes_out_not_correct(monkeypatch, capsys):
    """The driver itself, on XLA:CPU at toy size, with the engine's answer
    corrupted on its way to the comparison: an orbit keyed apart."""
    driver = _load("drivers", "symmetry")
    recheck = _load("drivers", "recheck")
    honest = recheck._result_dict
    ctx = dict(lib.resolve(CELL), seed=7, seconds=0.5, trace=False,
               rehearsal=True, t0=0.0)
    out = driver.run(dict(ctx))
    assert out["correct"] is True and out["failed"] == 0
    assert out["artifacts"]["symmetry_form"] == "sorted"
    assert out["artifacts"]["after"]["gauges"]["symmetry.group_order"] == 6
    import lib as harness
    loaded = harness.load_module

    def load(path, name):
        mod = loaded(path, name)
        if name == "bench_driver_recheck":
            mod._result_dict = lambda res, sess: dict(
                honest(res, sess), distinct=res.distinct + 1)
        return mod
    monkeypatch.setattr(driver, "load_module", load)
    out = driver.run(dict(ctx))
    assert out["correct"] is False and out["failed"] == out["attempted"] > 0
    assert "distinct: program 516 reference 515 gap 1 limit 0 FAILED" in \
        capsys.readouterr().out


def test_an_engine_that_does_not_reduce_ends_the_run_at_once(monkeypatch):
    """How the parent of PR 47 must end: no result, BenchFailure (run.py's
    exit 2) right after the build.  Here: the group pushed over the
    unrolled form's limit and the sorted form refused, as the parent has
    none."""
    from jaxmc.compile import symmetry2

    def refuse(*_):
        raise symmetry2._NotSortable("as the parent")
    monkeypatch.setattr(symmetry2, "_member_lanes", refuse)
    monkeypatch.setenv("JAXMC_SYM_GROUP_LIMIT", "2")
    driver = _load("drivers", "symmetry")
    ctx = dict(lib.resolve(CELL), seed=7, seconds=0.5, trace=False,
               rehearsal=True, t0=0.0)
    with pytest.raises(lib.BenchFailure, match="does not apply the cfg's "
                                               "SYMMETRY on the device"):
        driver.run(ctx)
    # ... and the unrolled form is not the form this cell times
    monkeypatch.setenv("JAXMC_SYM_GROUP_LIMIT", "64")
    with pytest.raises(lib.BenchFailure, match="'unrolled'"):
        driver.run(ctx)
