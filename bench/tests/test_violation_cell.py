"""The cell `desk-violation-4p` (added in PR 44): the manifest's new entries
found BY NAME (lists compared with `>=`, so a later cell may be appended),
the cell's files, the plain reference against the pins and the hand counts,
the judge of behaviours refusing a corrupted one, the six new readers on a
hand-made run and on the parent's (nothing to read: None), the control of
`correct` coming out not correct, the CPU rehearsal ending without a result
object, and a driver fed a corrupted trace coming out `correct: false`."""

import json
import os
import subprocess
import sys

import pytest

import lib

CELL, CONFIG, MIX = ("desk-violation-4p", "desk-violation-1chip",
                     "violation-deep-4p")
NEW = ("trace_walk_device_s", "trace_log_device_s", "trace_host_s",
       "trace_rows_expanded", "log_mb", "trace_walk_hbm_roofline")
BM = lib.load_json(os.path.join(lib.ROOT, "BENCHMARK.json"))


def _load(kind, name):
    return lib.load_module(os.path.join(lib.BENCH, kind, name + ".py"),
                           f"bench_{kind}_{name}")


def test_the_entries_in_the_manifest_by_name():
    conf = {c["name"]: c for c in BM["configs"]}[CONFIG]
    assert conf["file"] == "bench/configs/desk-violation-1chip.json"
    assert conf["reduced"] == [] and len(conf["source"]) <= 200
    assert "README.md:265-321" in conf["source"]
    assert conf["source"] == lib.load_json(
        os.path.join(lib.ROOT, conf["file"]))["source"]
    cell = {w["name"]: w for w in BM["workloads"]}[CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": MIX,
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert CELL in e2e["states_per_s"]["workloads"]
    by_name = {m["name"]: m for m in BM["per_layer"]}
    # every accepted metric that lists desk-deep-4p (the same engine at
    # the same size, no_trace) lists this cell too
    deep = {n for n, m in by_name.items()
            if "desk-deep-4p" in m.get("workloads", ())}
    assert len(deep) >= 26
    for name in deep:
        assert CELL in by_name[name]["workloads"], name
    for name in NEW:
        m = by_name[name]
        assert set(m["workloads"]) >= {CELL} and m["moves"] == "states_per_s"
        assert lib.NAME_RE.match(name) and lib.UNIT_RE.match(m["unit"])
    assert by_name["trace_walk_hbm_roofline"]["unit"] == "%"
    # new entries stand at the end of their lists
    assert [m["name"] for m in BM["per_layer"]][-6:] == list(NEW)
    assert BM["workloads"][-1]["name"] == CELL
    assert BM["configs"][-1]["name"] == CONFIG
    four = sum(1 for w in BM["workloads"] if w["chips"] == 4)
    assert four <= len(BM["workloads"]) // 2


def test_the_cell_resolves_to_files_that_exist():
    res = lib.resolve(CELL)
    conf, mix, pins = res["config"], res["mix"], res["pins"]
    assert conf["name"] == CONFIG and conf["chips"] == 1
    assert conf["reduced"] == {} and conf["architecture"] is None
    assert {"NoMoneyCreated", "scale", "res_caps"} <= set(conf["assumed"])
    deep = lib.resolve("desk-deep-4p")
    assert conf["session"] == deep["config"]["session"]
    assert (mix["driver"], mix["reference"]) == ("counterexample",
                                                 "transfer_violation")
    assert mix["session"] == {"resident": True, "no_trace": False}
    assert mix["use_pinned_caps"] is True and mix["trace_searches"] == 1
    for path in (mix["spec"], mix["cfg"]):
        assert os.path.isfile(os.path.join(lib.ROOT, path)), path
    # the module EXTENDS the spec every other cell checks, in its directory
    spec = open(os.path.join(lib.ROOT, mix["spec"])).read()
    assert "EXTENDS transfer_scaled" in spec
    assert os.path.dirname(mix["spec"]) == \
        os.path.dirname(deep["mix"]["spec"])
    assert os.path.isfile(res["driver_path"])
    assert [m["name"] for m in res["end_to_end"]] == ["states_per_s",
                                                      "setup_s"]
    names = {m["name"] for m in res["per_layer"]}
    assert names >= set(NEW) | {"dispatches_per_search", "hbm_peak_mb",
                                "program_hbm_mb", "expand_device_s"}
    for name in names:
        assert os.path.isfile(res["reader_path"](name)), name
    # the first four capacities are desk-deep-4p's: the same program but
    # for the log
    caps = dict(pins["res_caps"])
    log_cap = caps.pop("LogCap")
    assert caps == deep["pins"]["res_caps"]
    table = conf["scale"]["table_bytes"]
    assert table["the resident program's capacity-sized tables, the four "
                 "above (search.table_bytes)"] == 4 * (
        caps["SC"] * 5 + caps["FCap"] * 2 + caps["AccCap"] * 7
        + (log_cap + caps["FCap"]) * 2)
    assert pins["logged_rows"] <= log_cap < 4 * pins["logged_rows"]


def test_reference_against_the_pins_and_the_hand_counts():
    res = lib.resolve(CELL)
    ref_mod = _load("reference", "transfer_violation")
    # ISSUE 44's hand counts, from the other reference's step relation
    for (n, m), want in (((2, 3), (247, 166, 6)),
                         ((3, 12), (244767, 132309, 6)),
                         ((4, 8), (2297472, 1102952, 6))):
        got = ref_mod.explore(n, m)
        assert (got["generated"], got["distinct"], got["diameter"]) == want
        assert got["invariant"] == "NoMoneyCreated" and got["which"] == 1
    # the full rung, on a permuted cfg as a run does (seconds in numpy)
    src = open(os.path.join(lib.ROOT, res["mix"]["cfg"])).read()
    n, m, invs = ref_mod.parse_cfg(lib.permute_cfg(src, 2 ** 31 + 44))
    assert (n, m, invs) == (4, 12, ["AliceBounded", "NoMoneyCreated"])
    ref = ref_mod.explore(n, m, invs)
    pins = res["pins"]
    lib.check_pins(ref, pins)
    assert ref["levels"] == pins["levels"] and ref["ok"] is False
    assert ref["violating"] == pins["violating"] == 67392
    assert (ref["generated"], ref["distinct"], ref["diameter"]) == \
        (11514240, 5524224, 6)
    # levels 0-5 of the whole model's pins: the same graph, cut at the stop
    whole = lib.resolve("desk-deep-4p")["pins"]["levels"]
    assert ref["levels"] == whole[:6]
    # lib.reference_answer and control.py call explore(n, m, key_bits=...)
    assert lib.reference_answer(res["mix"], src)["generated"] == 11514240


def test_the_reference_imports_nothing_of_jaxmc():
    src = open(os.path.join(lib.BENCH, "reference",
                            "transfer_violation.py")).read()
    imports = [ln for ln in src.splitlines()
               if ln.startswith(("import ", "from "))]
    assert sorted(imports) == ["from __future__ import annotations",
                               "import numpy as np", "import re"]


def _witness():
    """A behaviour written by hand: p1 and p2 race, 2 x 3."""
    procs = ("p1", "p2")
    money = {"p1": 2, "p2": 3}

    def st(alice, bob, pc1, pc2):
        return {"alice": alice, "bob": bob, "money": dict(money),
                "pc": dict(zip(procs, (pc1, pc2)))}
    states = [st(3, 0, "check", "check"), st(3, 0, "debit", "check"),
              st(3, 0, "debit", "debit"), st(1, 0, "credit", "debit"),
              st(-2, 0, "credit", "credit"), st(-2, 2, "done", "credit"),
              st(-2, 5, "done", "done")]
    labels = ["Initial predicate", "Check(p1)", "Check(p2)", "Debit(p1)",
              "Debit(p2)", "Credit(p1)", "Credit(p2)"]
    return states, labels


def test_the_judge_accepts_any_witness_and_refuses_a_corrupted_one():
    ref_mod = _load("reference", "transfer_violation")
    states, labels = _witness()
    judge = lambda s, lb, **kw: ref_mod.check_trace(  # noqa: E731
        s, lb, 2, 3, "NoMoneyCreated", **kw)
    assert judge(states, labels, min_len=7) == \
        (True, "a behaviour of Spec that ends in the violation")
    # the other pair of labels for the same race is a witness too
    other = [dict(s, money={"p1": 3, "p2": 2}) for s in states]
    assert not judge(other, labels, min_len=7)[0]  # p1 now debits 3
    swapped = states[:2] + [states[3], states[2]] + states[4:]
    assert "step 2" in judge(swapped, labels, min_len=7)[1]
    replaced = states[:-1] + [dict(states[-1], bob=3)]
    assert not judge(replaced, labels, min_len=7)[0]
    assert "shortest" in judge(states + [states[-1]],
                               labels + ["Terminating"], min_len=7)[1]
    # ... and without the length rule the stutter is caught as a state
    # that violates before the end
    assert "violates" in judge(states + [states[-1]],
                               labels + ["Terminating"])[1]
    assert "first state" in judge(states[1:], labels[:1] + labels[2:])[1]
    assert "names no action" in judge(states, labels[:-1] + ["Bogus"])[1]
    assert "malformed" in judge([{"alice": 3}], ["Initial predicate"])[1]
    # the invariant the trace is judged by is the one NAMED
    assert not ref_mod.check_trace(states, labels, 2, 3, "AliceSolvent")[0]
    assert ref_mod.check_trace(states[:5], labels[:5], 2, 3,
                               "AliceSolvent", min_len=5)[0]


def test_the_control_comes_out_not_correct():
    """bench/control.py on this cell: the reference with its dedup key
    narrowed, through the harness's own comparison."""
    control = lib.load_module(os.path.join(lib.BENCH, "control.py"),
                              "bench_control")
    res = lib.resolve(CELL)
    toy = res["mix"]["rehearsal_cfg"].replace("{p1, p2}", "{p1, p2, p3}")
    ref = lib.reference_answer(res["mix"], toy)
    got = control.control_answer(res["mix"], toy, 4)
    got["truncated"] = False
    assert ref["ok"] is False and (got["distinct"] < ref["distinct"]
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


def test_the_counter_readers_by_hand_and_on_the_parent():
    rows, log = _load("layers", "trace_rows_expanded"), _load("layers",
                                                              "log_mb")
    pins = lib.resolve(CELL)["pins"]
    before = {"search.trace_rows_expanded": 1000, "search.log_bytes": 80,
              "search.trace_len": 7}
    after = {"search.trace_rows_expanded": 1000 + 2 * 1820160,
             "search.log_bytes": 80 + 2 * 8 * pins["logged_rows"],
             "search.trace_len": 21}
    run = _run((before, after))
    assert rows.read(run) == 1820160.0
    assert log.read(run) == 8 * pins["logged_rows"] / 1e6 == 29.12256
    # the parent has no such counter: nothing to read, the metric is left
    # out; the trace readers find no trace
    for name in NEW:
        assert _load("layers", name).read(_run()) is None, name
    assert _load("layers", "trace_walk_hbm_roofline").read(run) is None


def test_walk_bytes_and_the_roofline_by_hand(monkeypatch):
    shapes = lib.load_module(os.path.join(lib.BENCH, "shapes_trace.py"),
                             "bench_shapes_trace")
    assert shapes.walk_bytes(1820160, 7, 2) == (1820160 + 7) * 8
    assert shapes.walk_bytes(0, 7, 2) == 56
    import spans
    before = {"search.trace_rows_expanded": 0, "search.trace_len": 0}
    after = {"search.trace_rows_expanded": 1820160, "search.trace_len": 7}
    run = _run((before, after), searches=1)
    # a traced search whose walk took 0.01 device seconds
    monkeypatch.setattr(spans, "of_run", lambda r: {
        "searches": 1, "scoped": True, "named": True,
        "scope_s": {"jaxmc.trace.walk": 0.01, "jaxmc.trace.log": 0.002},
        "idle_s": {"jaxmc.trace.walk": 0.004, "jaxmc.trace.decode": 0.001,
                   "jaxmc.search.dispatch": 0.3}})
    share = _load("layers", "trace_walk_hbm_roofline").read(run)
    assert share == pytest.approx(
        100 * ((1820160 + 7) * 8 / 819e9) / 0.01)
    assert 0 < share < 1  # a floor of bytes against a re-expansion
    assert _load("layers", "trace_walk_device_s").read(run) == 0.01
    assert _load("layers", "trace_log_device_s").read(run) == 0.002
    assert _load("layers", "trace_host_s").read(run) == \
        pytest.approx(0.005)
    # a trace of the program before PR 44: scoped, but not these scopes
    monkeypatch.setattr(spans, "of_run", lambda r: {
        "searches": 1, "scoped": True, "named": True,
        "scope_s": {"jaxmc.expand": 0.01}, "idle_s": {}})
    for name in ("trace_walk_device_s", "trace_log_device_s",
                 "trace_host_s", "trace_walk_hbm_roofline"):
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
    assert "trace: 7 states, shortest 7" in p.stdout
    assert "bench: trace_rows_expanded = " in p.stdout
    assert "bench: log_mb = " in p.stdout
    assert "bench: table_mb = " in p.stdout


def test_a_corrupted_trace_comes_out_not_correct(monkeypatch, capsys):
    """The driver itself, on XLA:CPU at toy size, with the engine's answer
    corrupted on its way to the judge: a step swapped in every search."""
    driver = _load("drivers", "counterexample")
    honest = driver.verdict_of

    def corrupted(res):
        v = honest(res)
        v["states"][2], v["states"][3] = v["states"][3], v["states"][2]
        return v
    ctx = dict(lib.resolve(CELL), seed=7, seconds=0.5, trace=False,
               rehearsal=True, t0=0.0)
    out = driver.run(dict(ctx))
    assert out["correct"] is True and out["failed"] == 0
    assert len(out["artifacts"]["behaviour"]) == 7
    monkeypatch.setattr(driver, "verdict_of", corrupted)
    out = driver.run(dict(ctx))
    assert out["correct"] is False and out["failed"] == out["attempted"] > 0
    assert "trace: 7 states, shortest 7: step " in capsys.readouterr().out


def test_an_engine_that_keeps_no_trace_ends_the_run_at_once(monkeypatch):
    """How the parent of PR 44 must end: no result, BenchFailure (run.py's
    exit 2).  Here: the same options with the trace given up."""
    driver = _load("drivers", "counterexample")
    res = lib.resolve(CELL)
    res["mix"] = dict(res["mix"], session={"resident": True,
                                           "no_trace": True})
    ctx = dict(res, seed=7, seconds=0.5, trace=False, rehearsal=True, t0=0.0)
    with pytest.raises(lib.BenchFailure, match="keeps no counterexample"):
        driver.run(ctx)
