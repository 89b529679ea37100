"""The cell `desk-constraint-4p` (added in PR 51): the manifest's new entries
found BY NAME (lists compared with `>=`, so a later cell may be appended),
the cell's files, the plain reference against the pins and a hand count, the
four new readers on a hand-made run and on the parent's (nothing to read:
None), the control of `correct` coming out not correct, the CPU rehearsal
ending without a result object, a driver fed a miscounted search coming out
`correct: false`, a cfg without the CONSTRAINT line refused, and the guard:
an engine that does not judge the CONSTRAINT on the device — or does not say
so itself, as the parent of PR 51 — ends the run before any search."""

import json
import os
import subprocess
import sys

import pytest

import lib

CELL, CONFIG, MIX = ("desk-constraint-4p", "desk-constraint-1chip",
                     "recheck-constraint-4p")
NEW = ("constraint_device_s", "constraint_fill", "discarded_share",
       "constraint_hbm_roofline")
BM = lib.load_json(os.path.join(lib.ROOT, "BENCHMARK.json"))


def _load(kind, name):
    return lib.load_module(os.path.join(lib.BENCH, kind, name + ".py"),
                           f"bench_{kind}_{name}")


def test_the_entries_in_the_manifest_by_name():
    conf = {c["name"]: c for c in BM["configs"]}[CONFIG]
    assert conf["file"] == "bench/configs/desk-constraint-1chip.json"
    assert conf["reduced"] == ["MaxMoney"] and len(conf["source"]) <= 200
    assert "MCInnerFIFO.cfg" in conf["source"]
    assert conf["source"] == lib.load_json(
        os.path.join(lib.ROOT, conf["file"]))["source"]
    cell = {w["name"]: w for w in BM["workloads"]}[CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": MIX,
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert CELL in e2e["states_per_s"]["workloads"]
    by_name = {m["name"]: m for m in BM["per_layer"]}
    # every accepted metric that lists desk-symmetry-5p (the same engine
    # at the same capacities, no_trace) lists this cell too, but for the
    # canonicaliser's own
    sym = {n for n, m in by_name.items()
           if "desk-symmetry-5p" in m.get("workloads", ())
           and not n.startswith("canon_")}
    assert len(sym) >= 29
    for name in sym:
        assert CELL in by_name[name]["workloads"], name
    for name in by_name:
        if name.startswith("canon_"):
            assert CELL not in by_name[name]["workloads"], name
    for name in NEW:
        m = by_name[name]
        assert set(m["workloads"]) >= {CELL} and m["moves"] == "states_per_s"
        assert lib.NAME_RE.match(name) and lib.UNIT_RE.match(m["unit"])
    assert by_name["constraint_hbm_roofline"]["unit"] == "%"
    assert by_name["constraint_device_s"]["source"] == "device_trace"
    names = [m["name"] for m in BM["per_layer"]]
    assert names.index("tier_verify_share") < min(names.index(n)
                                                  for n in NEW)
    assert [w["name"] for w in BM["workloads"]].index(CELL) > \
        [w["name"] for w in BM["workloads"]].index("desk-symmetry-5p")
    four = sum(1 for w in BM["workloads"] if w["chips"] == 4)
    assert four <= len(BM["workloads"]) // 2


def test_the_cell_resolves_to_files_that_exist():
    res = lib.resolve(CELL)
    conf, mix, pins = res["config"], res["mix"], res["pins"]
    assert conf["name"] == CONFIG and conf["chips"] == 1
    assert list(conf["reduced"]) == ["MaxMoney"]
    assert conf["architecture"] is None
    assert {"Procs", "MaxTries", "res_caps", "compile_cache"} <= \
        set(conf["assumed"])
    deep = lib.resolve("desk-deep-4p")
    assert conf["session"] == deep["config"]["session"]
    # the desk's options and no other: no flag, no layout sample of its own
    assert mix["session"] == deep["mix"]["session"] == \
        {"resident": True, "no_trace": True}
    assert (mix["driver"], mix["reference"], mix["constraints"]) == \
        ("constraint", "transfer_retry", ["TriesBounded"])
    assert mix["use_pinned_caps"] is True and mix["trace_searches"] == 1
    for path in (mix["spec"], mix["cfg"]):
        assert os.path.isfile(os.path.join(lib.ROOT, path)), path
    # the module EXTENDS the spec every other cell checks, in its
    # directory, and retypes nothing of it
    spec = open(os.path.join(lib.ROOT, mix["spec"])).read()
    body = [ln for ln in spec.splitlines()
            if ln.strip() and not ln.startswith(("\\*", "---", "==="))]
    assert body[:3] == ["EXTENDS transfer_scaled", "CONSTANT MaxTries",
                        "VARIABLE tries"]
    assert not [ln for ln in body if ln.startswith(
        ("Check(", "Debit(", "Credit(", "Init ==", "AliceBounded =="))]
    assert os.path.dirname(mix["spec"]) == \
        os.path.dirname(deep["mix"]["spec"])
    cfg = open(os.path.join(lib.ROOT, mix["cfg"])).read()
    assert cfg.split() == (
        "SPECIFICATION SpecR INVARIANT AliceBounded CONSTRAINT TriesBounded "
        "CONSTANTS Procs = {p1, p2, p3, p4} MaxMoney = 4 MaxTries = 2"
    ).split()
    # the seed permutes the cfg and leaves the CONSTRAINT line as it stands
    assert "CONSTRAINT TriesBounded\n" in lib.permute_cfg(cfg, 2 ** 31 + 51)
    # tier-1 reads a copy under specs/
    assert open(os.path.join(lib.ROOT, "specs",
                             "transfer_retry.tla")).read() == spec
    assert os.path.isfile(res["driver_path"])
    assert [m["name"] for m in res["end_to_end"]] == ["states_per_s",
                                                      "setup_s"]
    names = {m["name"] for m in res["per_layer"]}
    assert names >= set(NEW) | {"dispatches_per_search", "hbm_peak_mb",
                                "program_hbm_mb", "expand_device_s",
                                "compact_device_s", "window_fill"}
    assert not [n for n in names if n.startswith("canon_")]
    for name in names:
        assert os.path.isfile(res["reader_path"](name)), name
    # desk-symmetry-5p's capacities
    caps = pins["res_caps"]
    assert caps == lib.resolve("desk-symmetry-5p")["pins"]["res_caps"]
    scale = conf["scale"]
    assert scale["table_bytes"][
        "the resident program's capacity-sized tables, the three above "
        "(search.table_bytes)"] == 4 * (
        caps["SC"] * 5 + caps["FCap"] * 2 + caps["AccCap"] * 7)
    for key, pin in (("generated", "generated"), ("distinct", "distinct"),
                     ("rows_fingerprinted", "fingerprinted"),
                     ("rows_discarded", "discarded")):
        assert scale[key] == pins[pin], key
    assert scale["levels"] == len(pins["levels"]) == pins["diameter"] + 1
    assert scale["largest_frontier"] == max(
        max(f, n) for f, _, n in pins["levels"]) <= caps["FCap"]
    assert scale["widest_level_candidates"] == max(
        c for _, c, _ in pins["levels"])
    assert scale["widest_level_new_rows"] == max(
        pins["fingerprinted_levels"])
    assert scale["levels_with_discards"] == sum(
        1 for (_, _, n), e in zip(pins["levels"],
                                  pins["fingerprinted_levels"]) if e > n)
    assert (scale["Procs"], scale["MaxMoney"], scale["MaxTries"]) == \
        (pins["procs"], pins["max_money"], pins["max_tries"])
    assert mix["row_lanes"] == 2 + 3 * pins["procs"]


def test_reference_against_the_pins_and_a_hand_count():
    res = lib.resolve(CELL)
    ref_mod = _load("reference", "transfer_retry")
    # one process, MaxMoney 1, MaxTries 0, by hand: check, debit, credit,
    # done and the one retry, which is generated, fingerprinted, discarded
    one = ref_mod.explore(1, (1, 0))
    assert (one["generated"], one["distinct"], one["diameter"], one["ok"],
            one["fingerprinted"], one["discarded"]) == (5, 4, 3, True, 5, 1)
    for size, want in (((3, (2, 1)), (16553, 5515, 17, 8734, 3219)),
                       ((2, (3, 2)), (2587, 1289, 18, 1655, 366)),
                       ((2, (2, 3)), (1752, 874, 20, 1062, 188))):
        got = ref_mod.explore(*size)
        assert (got["generated"], got["distinct"], got["diameter"],
                got["fingerprinted"], got["discarded"]) == want
    # the full rung, on a permuted cfg as a run does (~15 s in numpy)
    src = open(os.path.join(lib.ROOT, res["mix"]["cfg"])).read()
    text = lib.permute_cfg(src, 2 ** 31 + 51)
    assert ref_mod.parse_cfg(text) == (4, (4, 2), ["AliceBounded"])
    ref = lib.reference_answer(res["mix"], text)
    pins = res["pins"]
    lib.check_pins(ref, pins)
    assert ref["levels"] == pins["levels"] and ref["ok"] is True
    assert ref["fingerprinted_levels"] == pins["fingerprinted_levels"]
    assert (ref["generated"], ref["distinct"], ref["diameter"],
            ref["fingerprinted"], ref["discarded"]) == \
        (33280360, 8320026, 34, 12929810, 4609784) == tuple(
            pins[k] for k in ("generated", "distinct", "diameter",
                              "fingerprinted", "discarded"))


def test_a_cfg_without_the_constraint_line_is_refused():
    res = lib.resolve(CELL)
    text = open(os.path.join(lib.ROOT, res["mix"]["cfg"])).read()
    ref_mod = _load("reference", "transfer_retry")
    bare = text.replace("CONSTRAINT TriesBounded\n", "")
    assert bare != text
    with pytest.raises(ValueError, match="CONSTRAINT"):
        ref_mod.parse_cfg(bare)
    with pytest.raises(ValueError, match="CONSTRAINT"):
        lib.reference_answer(res["mix"], bare)
    # commented out is gone too
    with pytest.raises(ValueError, match="CONSTRAINT"):
        ref_mod.parse_cfg(text.replace("CONSTRAINT", "\\* CONSTRAINT"))


def test_the_reference_imports_nothing_of_jaxmc():
    src = open(os.path.join(lib.BENCH, "reference",
                            "transfer_retry.py")).read()
    imports = [ln.strip() for ln in src.splitlines()
               if ln.strip().startswith(("import ", "from "))]
    assert sorted(set(imports)) == [
        "from __future__ import annotations", "import json",
        "import numpy as np", "import re", "import sys", "import time"]
    assert "jaxmc" not in src.replace("nothing of jaxmc", "")


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


def _run(counters=None, searches=2, reference=None):
    res = lib.resolve(CELL)
    a, b = counters or ({}, {})
    art = {"searches": searches,
           "at_window": {"counters": a, "gauges": {}},
           "after": {"counters": b, "gauges": {}}}
    if reference:
        art["reference"] = reference
    out = {"trace_dir": None, "device": {"kind": "TPU v5 lite"},
           "artifacts": art}
    return {"out": out, "trace": None, "mix": res["mix"],
            "pins": res["pins"], "cell": res["cell"],
            "bench_dir": lib.BENCH}


def test_the_readers_by_hand_and_on_the_parent(monkeypatch):
    pins = lib.resolve(CELL)["pins"]
    init = pins["levels"][0][0]
    kept, gone = pins["distinct"] - init, pins["discarded"]
    slots = len(pins["levels"]) * pins["res_caps"]["AccCap"]
    names = ("search.rows_new", "search.rows_discarded",
             "search.slots_constrained")
    before = dict(zip(names, (kept, gone, slots)))          # the warm-up
    after = dict(zip(names, (3 * kept, 3 * gone, 3 * slots)))
    run = _run((before, after), reference={"levels": pins["levels"]})
    fill = _load("layers", "constraint_fill").read(run)
    assert fill == pytest.approx(100 * (kept + gone) / slots)
    assert 4.3 < fill < 4.5
    share = _load("layers", "discarded_share").read(run)
    assert share == pytest.approx(100 * gone / pins["fingerprinted"])
    assert 35.6 < share < 35.7
    # the parent has no such counters and no such scope: nothing to read,
    # the metrics are left out
    for name in NEW:
        assert _load("layers", name).read(_run()) is None, name
    # ... nor has a run of a cfg without a CONSTRAINT
    old = ({"search.rows_new": 5}, {"search.rows_new": 9})
    for name in NEW:
        assert _load("layers", name).read(_run(old)) is None, name
    assert _load("layers", "constraint_hbm_roofline").read(run) is None
    shapes = lib.load_module(os.path.join(lib.BENCH, "shapes_constraint.py"),
                             "bench_shapes_constraint")
    assert shapes.constraint_bytes(1000, 600, 2) == (1000 + 1200) * 2 * 4
    assert shapes.constraint_bytes(0, 0, 2) == 0
    import spans
    # two traced searches whose branch took 1.8 device seconds
    monkeypatch.setattr(spans, "of_run", lambda r: {
        "searches": 2, "scoped": True, "named": True,
        "scope_s": {"jaxmc.constraint": 1.8, "jaxmc.compact": 0.7},
        "idle_s": {}})
    assert _load("layers", "constraint_device_s").read(run) == 0.9
    roof = _load("layers", "constraint_hbm_roofline").read(run)
    nbytes = shapes.constraint_bytes(2 * (kept + gone), 2 * kept, 2)
    assert roof == pytest.approx(100 * (nbytes / 819e9) / 1.8)
    assert 0 < roof < 1
    # a trace of the program before PR 51: scoped, but not this scope —
    # None, never a guess
    monkeypatch.setattr(spans, "of_run", lambda r: {
        "searches": 2, "scoped": True, "named": True,
        "scope_s": {"jaxmc.compact": 0.7, "jaxmc.scan": 0.2},
        "idle_s": {}})
    for name in ("constraint_device_s", "constraint_hbm_roofline"):
        assert _load("layers", name).read(run) is None, name


def _run_py(args):
    return subprocess.run(
        [sys.executable, os.path.join(lib.BENCH, "run.py")] + args,
        cwd=lib.ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", JAXMC_COMPILE_CACHE="off"))


def test_traced_rehearsal_reads_the_counters_and_gives_no_result():
    p = _run_py(["--workload", CELL, "--seed", "2147483999", "--seconds",
                 "1", "--trace", "1", "--rehearse-on-cpu"])
    assert p.returncode == 0, p.stderr[-2000:]
    assert "NOT a chip run" in p.stdout and "correct=True" in p.stdout
    for line in p.stdout.splitlines():
        try:
            assert "correct" not in json.loads(line)
        except ValueError:
            pass
    assert "8734 fingerprinted, 3219 of them discarded" in p.stdout
    assert "the program compiled ['TriesBounded']" in p.stdout
    assert ("compare search[0] rows_discarded: program 3219 reference 3219 "
            "gap 0 limit 0 ok") in p.stdout
    assert "bench: discarded_share = 36.855965" in p.stdout
    assert "bench: constraint_fill = " in p.stdout
    assert "bench: table_mb = " in p.stdout


def test_a_miscounted_search_comes_out_not_correct(monkeypatch, capsys):
    """The driver itself, on XLA:CPU at toy size, with the engine's answer
    corrupted on its way to the comparison: first a state too many, then a
    discard too few with every count right."""
    driver = _load("drivers", "constraint")
    recheck = _load("drivers", "recheck")
    honest = recheck._result_dict
    ctx = dict(lib.resolve(CELL), seed=7, seconds=0.5, trace=False,
               rehearsal=True, t0=0.0)
    out = driver.run(dict(ctx))
    assert out["correct"] is True and out["failed"] == 0
    assert out["artifacts"]["constraints_compiled"] == ["TriesBounded"]
    assert out["artifacts"]["after"]["gauges"]["constraint.compiled"] == 1
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
    assert "distinct: program 5516 reference 5515 gap 1 limit 0 FAILED" in \
        capsys.readouterr().out
    monkeypatch.setattr(driver, "load_module", loaded)
    honest_cmp = driver.compare_discarded
    monkeypatch.setattr(
        driver, "compare_discarded", lambda got, ref, label: honest_cmp(
            dict(got, rows_discarded=got["rows_discarded"] - 1), ref, label))
    out = driver.run(dict(ctx))
    assert out["correct"] is False and out["failed"] == out["attempted"] > 0
    said = capsys.readouterr().out
    assert "rows_discarded: program 3218 reference 3219 gap 1 limit 0 " \
           "FAILED" in said
    assert "distinct: program 5515 reference 5515 gap 0 limit 0 ok" in said


def test_an_engine_that_interprets_the_constraint_ends_the_run_at_once(
        monkeypatch):
    """No result, BenchFailure (run.py's exit 2) right after the build:
    where the engine hands the CONSTRAINT to the interpreter, and where it
    compiles it but does not say so — the program before PR 51."""
    from jaxmc.backend import bfs
    driver = _load("drivers", "constraint")
    ctx = dict(lib.resolve(CELL), seed=7, seconds=0.5, trace=False,
               rehearsal=True, t0=0.0)
    searched = []
    from jaxmc.session import CheckSession
    monkeypatch.setattr(CheckSession, "explore",
                        lambda self: searched.append(1))
    # (1) as the parent: compiled, and no gauge says so
    real_gauge = bfs.obs.Telemetry.gauge
    monkeypatch.setattr(
        bfs.obs.Telemetry, "gauge", lambda self, name, value:
        None if name == "constraint.compiled"
        else real_gauge(self, name, value))
    with pytest.raises(lib.BenchFailure, match="does not judge the cfg's "
                                               "CONSTRAINT"):
        driver.run(dict(ctx))
    monkeypatch.setattr(bfs.obs.Telemetry, "gauge", real_gauge)
    # (2) a constraint the kernel compiler refuses goes to the interpreter
    real_init = bfs.TpuExplorer.__init__

    def hybrid(self, *a, **kw):
        real_init(self, *a, **kw)
        self.fb_cons = [(nm, None, "as if refused")
                        for nm, _ in self.constraint_fns]
        self.constraint_fns = []
    monkeypatch.setattr(bfs.TpuExplorer, "__init__", hybrid)
    with pytest.raises(lib.BenchFailure, match="interpreted "
                                               r"\['TriesBounded'\]"):
        driver.run(dict(ctx))
    assert not searched
