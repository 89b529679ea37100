"""The cell `ci-cohort-4p` (added in PR 39): the manifest's new entries found
BY NAME (lists compared with `>=`, never by position), the cell's files, the
window's arithmetic, the shapes function on a hand-worked dispatch, its CPU
rehearsal, the readers on a rehearsed window and on a run that has nothing to
read, and a job answered wrongly coming out `correct: false`."""

import json
import os
import subprocess
import sys

import pytest

import lib

CELL, CONFIG, MIX = "ci-cohort-4p", "ci-cohort-1chip", "cohort-matrix-4p"
SERVE = "serve (daemon, queue, owner)"
NEW = {"cohort_build_s": ("s/commit", "program_span", "engines"),
       "cohort_launch_s": ("s/commit", "program_counter",
                           "XLA compile + cache"),
       "cohort_search_s": ("s/commit", "program_span", "engines"),
       "vstep_device_s": ("s/commit", "device_trace", "kernels"),
       "vstep_host_s": ("s/commit", "program_span", "engines"),
       "barrier_wait_s": ("s/commit", "program_counter", "engines"),
       "store_s": ("s/commit", "program_counter", "engines"),
       "vsteps_per_commit": ("count", "program_counter", "engines"),
       "lane_fill": ("%", "program_counter", "engines"),
       "cohort_occupancy": ("members", "program_counter", SERVE),
       "cohort_ckpt_s": ("s/commit", "program_span", "engines"),
       "vstep_hbm_roofline": ("%", "device_trace", "kernels")}
#: read the program's spans and counters of PR 39: None on the parent
NEEDS_PR39 = ("cohort_build_s", "cohort_launch_s", "cohort_search_s",
              "barrier_wait_s", "store_s", "lane_fill")
NEEDS_TRACE = ("vstep_device_s", "vstep_host_s", "vstep_hbm_roofline")
UNLISTED = ("dispatches_per_search", "hbm_peak_mb")
JOINED = ("serve_path_s", "queue_wait_s", "verdict_p50_s", "verdict_p95_s",
          "owner_compiles", "launch_s", "program_temp_mb", "program_hbm_mb")
BM = lib.load_json(os.path.join(lib.ROOT, "BENCHMARK.json"))


def _read(name, run):
    return lib.load_module(os.path.join(lib.BENCH, "layers", name + ".py"),
                           "bench_layer_" + name).read(run)


def _driver():
    return lib.load_module(os.path.join(lib.BENCH, "drivers", "cohort.py"),
                           "bench_driver_cohort")


def _ctx(seed=2147483777, trace=False, seconds=0.0):
    import time
    return dict(lib.resolve(CELL), seed=seed, seconds=seconds, trace=trace,
                rehearsal=True, t0=time.time())


def test_the_entries_in_the_manifest_by_name():
    conf = {c["name"]: c for c in BM["configs"]}[CONFIG]
    assert conf["file"] == "bench/configs/ci-cohort-1chip.json"
    assert conf["reduced"] == ["matrix", "runners"]
    assert len(conf["source"]) <= 200 and "Makefile:1-7" in conf["source"]
    assert conf["source"] == lib.load_json(
        os.path.join(lib.ROOT, conf["file"]))["source"]
    cell = {w["name"]: w for w in BM["workloads"]}[CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": MIX,
                    "chips": cell["chips"], "why": cell["why"]}
    assert cell["chips"] in (1, 4)
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert CELL in e2e["states_per_s"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    by_name = {m["name"]: m for m in BM["per_layer"]}
    for name, (unit, source, layer) in NEW.items():
        m = by_name[name]
        assert m["workloads"][:1] == [CELL]   # a later cohort cell may join
        assert (m["moves"], m["layer"]) == ("states_per_s", layer)
        assert (m["unit"], m["source"]) == (unit, source)
        assert lib.NAME_RE.match(name) and lib.UNIT_RE.match(unit)
    for name in UNLISTED:
        assert "workloads" not in by_name[name], name
    for name in JOINED:
        assert by_name[name]["workloads"] >= ["ci-stream-4p8", CELL], name
    # no other accepted metric took the new cell
    for m in BM["per_layer"]:
        if m["name"] not in NEW and m["name"] not in JOINED:
            assert CELL not in m.get("workloads", []), m["name"]
    four = [w for w in BM["workloads"] if w["chips"] == 4]
    assert len(four) <= len(BM["workloads"]) // 2
    assert len(BM["workloads"]) >= 8 and len(BM["configs"]) >= 6
    assert len(json.dumps(BM, indent=1)) < 64 * 1024


def test_the_cell_resolves_to_files_that_exist():
    res = lib.resolve(CELL)
    conf, mix = res["config"], res["mix"]
    assert conf["name"] == CONFIG and conf["chips"] == 1
    assert conf["architecture"] is None
    assert set(conf["reduced"]) == {"matrix", "runners"}
    assert {"sweep", "job_options", "poll_s", "compile_cache",
            "spool"} <= set(conf["assumed"])
    stream = lib.resolve("ci-stream-4p8")["config"]
    assert conf["daemon"] == stream["daemon"]      # to the letter
    assert conf["session"] == stream["session"]
    assert len(conf["guarantees"]) >= 8 and conf["memory"]
    assert (mix["driver"], mix["reference"]) == ("cohort",
                                                 "transfer_scaled")
    assert mix["runners"] == ["ci-a", "ci-b", "ci-c"]
    assert mix["cycle"] == ["edit", "edit"]
    assert mix["poll_s"] == 0.05
    assert mix["job_options"] == {"host_seen": True, "no_trace": True}
    assert [it["label"] for it in mix["suite"]] == ["4p5", "4p6", "4p7",
                                                    "4p8"]
    for item in mix["suite"]:
        assert os.path.isfile(os.path.join(lib.ROOT, item["cfg"]))
        assert os.path.isfile(os.path.join(lib.BENCH, "pins",
                                           item["pins"] + ".json"))
    # the largest member is ci-stream-4p8's and desk-recheck-4p8's own cfg
    desk = lib.resolve("desk-recheck-4p8")["mix"]
    assert (mix["suite"][-1]["cfg"], mix["suite"][-1]["pins"]) == \
        (desk["cfg"], desk["pins"])
    # bench/control.py reads the mix's `cfg` and `pins`: the long job's
    assert (mix["cfg"], mix["pins"]) == (desk["cfg"], desk["pins"])
    assert len(mix["rehearsal_suite"]) == len(mix["suite"])
    assert os.path.isfile(res["driver_path"])
    names = {m["name"] for m in res["per_layer"]}
    assert names >= set(NEW) | set(UNLISTED) | set(JOINED)
    for name in names:
        assert os.path.isfile(res["reader_path"](name)), name
    assert {m["name"] for m in res["end_to_end"]} == {"states_per_s",
                                                      "setup_s"}


def test_the_windows_arithmetic_from_the_pins_and_the_reference():
    """The four cfgs differ in MaxMoney alone; a commit generates
    9,811,608 states and a window of six 58,869,648 — by the pins AND by
    the plain reference run now."""
    mix = lib.resolve(CELL)["mix"]
    texts, total = [], 0
    for m, item in zip((5, 6, 7, 8), mix["suite"]):
        pins = lib.load_json(os.path.join(lib.BENCH, "pins",
                                          item["pins"] + ".json"))
        text = open(os.path.join(lib.ROOT, item["cfg"]),
                    encoding="utf-8").read()
        assert (pins["procs"], pins["max_money"]) == (4, m)
        ref = lib.reference_answer(mix, text)
        lib.check_pins(ref, pins)
        assert ref["ok"] and ref["diameter"] == 12
        texts.append(text.replace(f"MaxMoney = {m}", "MaxMoney = _"))
        total += pins["generated"]
    assert len(set(texts)) == 1
    assert total == mix["commit_generated"] == 9811608
    assert mix["window_commits"] == len(mix["runners"]) * len(mix["cycle"])
    assert total * mix["window_commits"] == mix["window_generated"] \
        == 58869648


def test_the_shapes_of_one_dispatch_by_hand():
    sc = lib.load_module(os.path.join(lib.BENCH, "shapes_cohort.py"),
                         "bench_shapes_cohort")
    # one member, two rows, one word, one arm, two key lanes, no constant:
    # frontier 8 + count 4 | cand 8, keys 16, three flags 6, dead 2,
    # assert 2, two scalars 8
    assert sc.vstep_bytes(1, 2, 1, 1, 2, 0) == 12 + 42
    mix = lib.resolve(CELL)["mix"]
    v = mix["vstep"]
    assert v["arms"] == 3 * 4 + 1 and v["state_words"] == mix["state_words"]
    assert v["key_lanes"] == 1 + mix["key_words"]
    assert (v["members"], v["chunk"]) == (len(mix["suite"]), 2048)
    assert sc.vstep_bytes_of(mix) == 65568 + 3416096 == 3481664
    assert sc.vstep_bytes_of({}) is None


@pytest.mark.parametrize("trace", ["small_tpu.xplane.pb",
                                   "small_tpu_scoped.xplane.pb"])
def test_the_device_seconds_a_dispatch_from_a_recorded_trace(trace, tmp_path):
    """`cohorts.dispatch_device_s` on the traces recorded on the chip, with
    their three `bench.search` spans standing in for dispatches: the
    window's busy seconds over the spans' COUNT (the trace's device clock
    runs off the host's: a span's borders cut nothing here)."""
    import cohorts
    import reduce
    path = os.path.join(lib.BENCH, "tests", "data", trace)
    assert cohorts.spans_in_window(path, span="bench.search") == 3
    assert cohorts.spans_in_window(path) == 0
    assert cohorts.spans_in_window(path, "no.such.window") is None
    # a run whose trace holds no dispatch span (the parent's): None
    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    os.symlink(path, str(d / "x.xplane.pb"))
    run = {"out": {"trace_dir": str(tmp_path)},
           "trace": reduce.reduce_trace(path)}
    assert cohorts.dispatches_traced(run) is None
    assert cohorts.dispatch_device_s(run) is None
    assert cohorts.dispatch_device_s({"out": {}, "trace": None}) is None


def test_the_driver_and_harness_stay_off_jax():
    for rel in ("drivers/cohort.py", "cohorts.py", "shapes_cohort.py"):
        src = open(os.path.join(lib.BENCH, rel), encoding="utf-8").read()
        assert "import jax" not in src and "from jax" not in src, rel


@pytest.fixture(scope="module")
def window():
    """One rehearsed window (XLA:CPU, toy size, one cycle a runner),
    driven through the driver in this process."""
    out = _driver().run(_ctx())
    return {"out": out, "trace": None, "mix": lib.resolve(CELL)["mix"],
            "pins": lib.resolve(CELL)["pins"], "bench_dir": lib.BENCH}


def test_a_rehearsed_window_is_correct_and_every_commit_one_cohort(window):
    out = window["out"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 24
    art = out["artifacts"]
    assert art["searches"] == 24 and art["commits"] == 6
    assert len(art["warmup"]) == 2 + 12
    for row in art["cohorts"]:
        assert row["occupancy"] == [4] * 4, row
        assert row["mates"] == [3] * 4, row
    # the warm-up commits ran whole too: the program is made in set-up
    assert [j["serve"].get("batch_occupancy") for j in art["warmup"]] == \
        [None, None] + [4] * 12
    assert out["values"]["states_per_s"] > 0
    work = os.path.join(lib.ROOT, ".bench_work", CELL)
    assert not os.path.exists(os.path.join(work, "spool", "ckpt"))
    pid = art["status"]["device_owner_pid"]
    assert not os.path.exists(f"/proc/{pid}")


def test_the_readers_on_the_rehearsed_window(window):
    got = {name: _read(name, window)
           for name in list(NEW) + list(UNLISTED) + list(JOINED)}
    for name in set(NEW) - set(NEEDS_TRACE) | set(JOINED):
        assert got[name] is not None and got[name] >= 0, name
    for name in NEEDS_TRACE:     # no trace in this run
        assert got[name] is None, name
    assert got["cohort_occupancy"] == 4.0
    assert got["vsteps_per_commit"] == 12.0    # 10 levels, 2 more for 3p5
    assert got["lane_fill"] == pytest.approx(100.0 * 42 / 48)
    assert got["dispatches_per_search"] == 0.0   # no `bfs.*` search site
    jobs = window["out"]["artifacts"]["jobs"]
    assert got["cohort_ckpt_s"] == pytest.approx(
        sum(j["phases"]["checkpoint.write"] for j in jobs) / 6)
    assert got["barrier_wait_s"] == pytest.approx(
        sum(j["counters"]["batch.barrier_wait_s"] for j in jobs) / 6)
    # a commit's build, first call and run lie inside its owner wall
    wall = sum({j["serve"]["job_wall_s"] for j in jobs}) / 6
    assert got["cohort_build_s"] + got["cohort_search_s"] <= wall
    assert got["cohort_launch_s"] < got["cohort_search_s"]


def test_the_readers_where_there_is_nothing_to_read():
    """Another driver's run, artifacts without the program's spans and
    counters (the parent's), an untraced run: None, never an exception."""
    bare = {"runner": "ci-a", "commit": 1, "kind": "edit", "label": "4p8",
            "id": "j1", "status": "done", "client_s": 2.0, "t_post": 0.0,
            "t_result": 2.0, "serve": {"batch_occupancy": 4,
                                       "batch_dispatches": 911,
                                       "batched_with": ["j2", "j3", "j4"]},
            "result": {}, "phases": {"checkpoint.write": 0.5},
            "counters": {}, "gauges": {}, "dispatches": {},
            "peak_bytes": 0, "env": {}}
    empty = {"out": {"artifacts": {}}, "trace": None, "mix": {}, "pins": {}}
    for name in NEW:
        assert _read(name, empty) is None, name
    parent = {"out": {"artifacts": {"jobs": [bare], "commits": 1},
                      "trace_dir": None}, "trace": None, "mix": {},
              "pins": {}}
    for name in NEW:
        value = _read(name, parent)
        if name in NEEDS_PR39 + NEEDS_TRACE:
            assert value is None, (name, value)
    assert _read("vsteps_per_commit", parent) == 911.0
    assert _read("cohort_occupancy", parent) == 4.0
    assert _read("cohort_ckpt_s", parent) == 0.5


def _has_result_line(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except ValueError:
            pass
    return False


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_ends_without_a_result_object(trace):
    p = subprocess.run(
        [sys.executable, os.path.join(lib.BENCH, "run.py"), "--workload",
         CELL, "--seed", "2147483999", "--seconds", "1", "--trace", trace,
         "--rehearse-on-cpu"], cwd=lib.ROOT, capture_output=True, text=True,
        timeout=900, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    assert "NOT a chip run" in p.stdout and "correct=True" in p.stdout
    assert "cohort_occupancy = 4.0" in p.stdout or trace == "0"
    assert "NOT one cohort" not in p.stdout
    assert not _has_result_line(p.stdout)


def test_a_job_answered_wrongly_comes_out_not_correct(monkeypatch):
    """One verdict of the window altered where the client reads it back
    (one distinct state lost): the run is `correct: false`, one failed."""
    drv = _driver()
    real, seen = drv.fc.Client.call, {"n": 0}

    def lossy(self, method, path, body=None):
        code, obj = real(self, method, path, body)
        if path.endswith("/result") and code == 200:
            seen["n"] += 1
            if seen["n"] == 20:      # past the 14 of set-up
                obj["result"]["distinct"] -= 1
        return code, obj

    monkeypatch.setattr(drv.fc.Client, "call", lossy)
    out = drv.run(_ctx(seed=5))
    assert out["correct"] is False and out["failed"] == 1
    assert out["attempted"] == 24


def test_the_control_comes_out_not_correct():
    p = subprocess.run(
        [sys.executable, os.path.join(lib.BENCH, "control.py"),
         "--workload", CELL, "--seeds", "11"], cwd=lib.ROOT,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "1 of 1 seeds came out not correct" in p.stdout
