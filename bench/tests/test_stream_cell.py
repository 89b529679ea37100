"""The cell `ci-stream-4p8` (added in PR 36): the manifest's new entries found
BY NAME (lists compared with `>=`, never by position), the cell's files, its
CPU rehearsal, the readers on a rehearsed window and on a run that has
nothing to read, a job answered wrongly coming out `correct: false`, and a
daemon that dies leaving no process and no result."""

import json
import os
import signal
import subprocess
import sys

import pytest

import lib

CELL, CONFIG, MIX = "ci-stream-4p8", "ci-stream-1chip", "stream-commits-4p8"
SERVE = "serve (daemon, queue, owner)"
BUILD = "entry, front + analysis, kernel build"
NEW = {"job_build_s": ("s/job", "program_span", BUILD),
       "job_cache_load_s": ("s/job", "program_counter",
                            "XLA compile + cache"),
       "job_search_s": ("s/job", "program_span", "engines"),
       "job_ckpt_s": ("s/job", "program_span", "engines"),
       "serve_path_s": ("s/job", "host_clock", SERVE),
       "queue_wait_s": ("s/job", "program_span", SERVE),
       "replay_verdict_s": ("s", "host_clock", SERVE),
       "verdict_p50_s": ("s", "host_clock", SERVE),
       "verdict_p95_s": ("s", "host_clock", SERVE),
       "build_idle_s": ("s/job", "program_span", BUILD),
       "warm_hit_share": ("%", "program_counter", SERVE),
       "owner_compiles": ("count", "program_counter", "XLA compile + cache"),
       "job_device_s": ("s/job", "device_trace", "kernels"),
       "job_search_idle_s": ("s/job", "program_span", "engines")}
# accepted metrics without a list that read a true number here; the two that
# find nothing to read (no `bench.search` span in the owner's trace; a count
# of ONE session's recompiles) carry the list of the cells accepted before
# this one; and the accepted ones with a list that took the cell, because the
# driver hands them the searched jobs' counters and gauges in the desk
# cells' shape
UNLISTED = ("dispatches_per_search", "hbm_peak_mb")
SIX_ONLY = ("search_hbm_roofline", "window_recompiles")
JOINED = ("launch_s", "seed_build_s", "seed_upload_s", "seed_mb_per_search",
          "program_temp_mb", "program_hbm_mb")
ACCEPTED = ["desk-recheck-4p8", "desk-default-3p", "desk-recheck-3p",
            "mesh-recheck-4p", "desk-deep-4p", "desk-ooc-4p8"]
BM = lib.load_json(os.path.join(lib.ROOT, "BENCHMARK.json"))


def _read(name, run):
    return lib.load_module(os.path.join(lib.BENCH, "layers", name + ".py"),
                           "bench_layer_" + name).read(run)


def _driver():
    return lib.load_module(os.path.join(lib.BENCH, "drivers", "stream.py"),
                           "bench_driver_stream")


def _ctx(seed=2147483777, trace=False, seconds=0.0):
    import time
    return dict(lib.resolve(CELL), seed=seed, seconds=seconds, trace=trace,
                rehearsal=True, t0=time.time())


def test_the_entries_in_the_manifest_by_name():
    conf = {c["name"]: c for c in BM["configs"]}[CONFIG]
    assert conf["file"] == "bench/configs/ci-stream-1chip.json"
    assert conf["reduced"] == ["suite", "runners"]
    assert len(conf["source"]) <= 200 and "Makefile:1-7" in conf["source"]
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
    for name, (unit, source, layer) in NEW.items():
        m = by_name[name]
        assert m["workloads"][:1] == [CELL]   # a later served cell may join
        assert (m["moves"], m["layer"]) == ("states_per_s", layer)
        assert (m["unit"], m["source"]) == (unit, source)
        assert lib.NAME_RE.match(name) and lib.UNIT_RE.match(unit)
    for name in UNLISTED:
        assert "workloads" not in by_name[name], name
    for name in SIX_ONLY:
        assert by_name[name]["workloads"] >= ACCEPTED, name
        assert CELL not in by_name[name]["workloads"], name
    for name in JOINED:
        assert by_name[name]["workloads"] >= ACCEPTED + [CELL], name
    # no other accepted metric took the new cell, and it brings no
    # roofline: the two device programs are the desk cells' own
    for m in BM["per_layer"]:
        if m["name"] not in NEW and m["name"] not in JOINED:
            assert CELL not in m.get("workloads", []), m["name"]
    assert not [n for n in by_name if n.endswith("_roofline")
                and n not in ("search_hbm_roofline",
                              "exchange_ici_roofline")]
    four = [w for w in BM["workloads"] if w["chips"] == 4]
    assert len(four) <= len(BM["workloads"]) // 2
    assert len(BM["workloads"]) >= 7 and len(BM["configs"]) >= 5


def test_the_cell_resolves_to_files_that_exist():
    res = lib.resolve(CELL)
    conf, mix = res["config"], res["mix"]
    assert conf["name"] == CONFIG and conf["chips"] == 1
    assert conf["architecture"] is None
    assert set(conf["reduced"]) == {"suite", "runners"}
    assert {"job_options", "edit_ratio", "poll_s",
            "compile_cache"} <= set(conf["assumed"])
    assert conf["daemon"] == ["-m", "jaxmc.serve", "run", "--workers", "2",
                              "--quiet"]
    one = lib.resolve("desk-recheck-4p8")
    assert conf["session"] == one["config"]["session"]
    assert len(conf["guarantees"]) >= 8
    # what a CI user is given, and nothing of the program's telemetry
    assert not any("station" in g or "span" in g
                   for g in conf["guarantees"])
    assert "trace_cycles" not in mix
    assert (mix["driver"], mix["reference"]) == ("stream",
                                                 "transfer_scaled")
    assert mix["runners"] == ["ci-a", "ci-b"]
    assert mix["cycle"] == ["edit", "edit", "edit", "rerun"]
    assert mix["poll_s"] == 0.05
    # the suite is the two resident desk cells' cfgs, pins and options: the
    # device programs are theirs
    for item, cellname in zip(mix["suite"], ("desk-recheck-3p",
                                             "desk-recheck-4p8")):
        desk = lib.resolve(cellname)
        assert item["cfg"] == desk["mix"]["cfg"]
        assert item["pins"] == desk["mix"]["pins"]
        assert mix["job_options"] == desk["mix"]["session"]
        assert os.path.isfile(os.path.join(lib.ROOT, item["cfg"]))
        assert os.path.isfile(os.path.join(lib.BENCH, "pins",
                                           item["pins"] + ".json"))
    assert len(mix["rehearsal_suite"]) == len(mix["suite"])
    # bench/control.py reads the mix's `cfg` and `pins`: the long job's
    assert (mix["cfg"], mix["pins"]) == (mix["suite"][1]["cfg"],
                                         mix["suite"][1]["pins"])
    assert os.path.isfile(res["driver_path"])
    names = {m["name"] for m in res["per_layer"]}
    assert names >= set(NEW) | set(UNLISTED) | set(JOINED)
    assert not names & set(SIX_ONLY)
    for name in names:
        assert os.path.isfile(res["reader_path"](name)), name
    assert {m["name"] for m in res["end_to_end"]} == {"states_per_s",
                                                      "setup_s"}


def test_the_driver_and_harness_stay_off_jax():
    for rel in ("drivers/stream.py", "served.py"):
        src = open(os.path.join(lib.BENCH, rel), encoding="utf-8").read()
        assert "import jax" not in src and "from jax" not in src, rel
    assert "jaxmc" not in [
        ln.split()[1].split(".")[0] for ln in open(os.path.join(
            lib.BENCH, "drivers", "stream.py"), encoding="utf-8")
        if ln.startswith(("import ", "from "))]


@pytest.fixture(scope="module")
def window():
    """One rehearsed window (XLA:CPU, toy size, one cycle a runner),
    driven through the driver in this process."""
    out = _driver().run(_ctx())
    return {"out": out, "trace": None, "mix": lib.resolve(CELL)["mix"],
            "pins": lib.resolve(CELL)["pins"], "bench_dir": lib.BENCH}


def test_a_rehearsed_window_is_correct_and_whole(window):
    out = window["out"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 16
    jobs = out["artifacts"]["jobs"]
    assert sum(1 for j in jobs if j["kind"] == "edit") == 12
    assert sum(1 for j in jobs if j["kind"] == "rerun") == 4
    assert out["artifacts"]["searches"] == 12
    assert {"states_per_s", "setup_s"} == set(out["values"])
    assert out["values"]["states_per_s"] > 0
    # the spool's checkpoints are gone, the daemon and its owner too
    work = os.path.join(lib.ROOT, ".bench_work", CELL)
    assert not os.path.exists(os.path.join(work, "spool", "ckpt"))
    pid = out["artifacts"]["status"]["device_owner_pid"]
    assert not os.path.exists(f"/proc/{pid}")


def test_the_readers_on_the_rehearsed_window(window):
    got = {name: _read(name, window)
           for name in list(NEW) + list(UNLISTED) + list(JOINED)}
    for name in ("job_build_s", "job_cache_load_s", "job_search_s",
                 "job_ckpt_s", "serve_path_s", "queue_wait_s",
                 "replay_verdict_s", "verdict_p50_s", "verdict_p95_s",
                 "owner_compiles") + JOINED:
        assert got[name] is not None and got[name] >= 0, name
    assert got["warm_hit_share"] == 25.0
    assert got["verdict_p95_s"] >= got["verdict_p50_s"]
    assert got["dispatches_per_search"] >= 1
    # what the joined readers read is the sum over the searched jobs
    jobs = window["out"]["artifacts"]["jobs"]
    searched = [j for j in jobs if j["kind"] == "edit"]
    assert got["launch_s"] == pytest.approx(
        sum(j["counters"]["dispatch.launch_s"] for j in searched) / 12)
    assert got["program_hbm_mb"] == pytest.approx(
        max(j["gauges"]["program.hbm_bytes"] for j in searched) / 1e6)
    # no trace in this run: the trace readers find nothing to read
    for name in ("build_idle_s", "job_device_s", "job_search_idle_s"):
        assert got[name] is None, name
    # a job's record clock and its owner wall lie inside its client wall:
    # what is left is the served path
    for j in jobs:
        assert j["t_post"] <= j["submitted_at"] <= j["started_at"] <= \
            j["finished_at"] <= j["t_result"], j
        assert j["serve"]["job_wall_s"] <= j["client_s"]


def test_the_readers_where_there_is_nothing_to_read():
    """Another driver's run, or artifacts without clocks and counters: None,
    never an exception."""
    bare = {"runner": "ci-a", "commit": 1, "kind": "edit", "label": "4p8",
            "status": "done", "client_s": 2.0, "t_post": 0.0,
            "t_result": 2.0, "serve": {}, "result": {}, "phases": {},
            "counters": {}, "gauges": {}, "dispatches": {},
            "peak_bytes": 0, "env": {}}
    for out in ({"artifacts": {}}, {"artifacts": {"jobs": [bare]},
                                    "trace_dir": None}):
        run = {"out": out, "trace": None, "mix": {}, "pins": {}}
        for name in NEW:
            value = _read(name, run)
            if name in ("verdict_p50_s", "verdict_p95_s") and \
                    out["artifacts"].get("jobs"):
                assert value == 2.0
            elif name == "warm_hit_share" and out["artifacts"].get("jobs"):
                assert value == 0.0
            else:
                assert value is None, (name, value)


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
        timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    assert "NOT a chip run" in p.stdout and "correct=True" in p.stdout
    assert "warm_hit_share = 25.0" in p.stdout or trace == "0"
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
            if seen["n"] == 7:
                obj["result"]["distinct"] -= 1
        return code, obj

    monkeypatch.setattr(drv.fc.Client, "call", lossy)
    out = drv.run(_ctx(seed=5))
    assert out["correct"] is False and out["failed"] == 1
    assert out["attempted"] == 16


def test_a_daemon_that_dies_leaves_no_process_and_no_result(monkeypatch):
    """SIGKILL the daemon in the middle of the window: the run raises (so
    run.py prints no result line and exits 2) and the owner, which a killed
    daemon cannot stop, is gone when it returns."""
    drv = _driver()
    real, seen = drv.fc.Client.call, {"posts": 0, "pids": []}

    def fatal(self, method, path, body=None):
        if method == "POST":
            seen["posts"] += 1
            if seen["posts"] == 6:   # warm-up's 2, then the window's 4th
                stamp = lib.load_json(os.path.join(
                    lib.ROOT, ".bench_work", CELL, "spool", "serve.json"))
                seen["pids"] = [stamp["pid"]] + drv._children(stamp["pid"])
                os.kill(stamp["pid"], signal.SIGKILL)
        return real(self, method, path, body)

    monkeypatch.setattr(drv.fc.Client, "call", fatal)
    with pytest.raises(lib.BenchFailure):
        drv.run(_ctx(seed=6))
    assert len(seen["pids"]) >= 2      # the daemon and at least its owner
    for pid in seen["pids"]:
        assert not drv._alive(pid), pid
