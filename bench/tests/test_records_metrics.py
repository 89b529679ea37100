"""The five readers of PR 34 (`seed_build_s`, `seed_upload_s`, `launch_s`,
`program_temp_mb`, `program_hbm_mb`): the manifest's entries found BY NAME,
None on a run of a program without the counter or gauge (the parent's), the
arithmetic on recorded ones, and a traced rehearsal in which the program's
counters and gauges reach them."""

import json
import os
import subprocess
import sys

import lib

NEW = {"seed_build_s": ("s/search", "engines"),
       "seed_upload_s": ("s/search", "engines"),
       "launch_s": ("s/search", "engines"),
       "program_temp_mb": ("MB", "kernels"),
       "program_hbm_mb": ("MB", "device")}
# the six cells of PR 34, by name; a later cell may be appended
CELLS = ("desk-recheck-4p8", "desk-default-3p", "desk-recheck-3p",
         "mesh-recheck-4p", "desk-deep-4p", "desk-ooc-4p8")
BM = lib.load_json(os.path.join(lib.ROOT, "BENCHMARK.json"))


def _read(name, run):
    return lib.load_module(os.path.join(lib.BENCH, "layers", name + ".py"),
                           "bench_layer_" + name).read(run)


def test_the_entries_in_the_manifest_by_name():
    by_name = {m["name"]: m for m in BM["per_layer"]}
    cells = [w["name"] for w in BM["workloads"]]
    layers = {m["layer"] for m in BM["per_layer"] if m["name"] not in NEW}
    for name, (unit, layer) in NEW.items():
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert len(m["workloads"]) >= len(CELLS)
        assert m["workloads"][:6] == list(CELLS) and set(CELLS) <= set(cells)
        assert (m["unit"], m["layer"], m["better"]) == (unit, layer, "lower")
        assert (m["source"], m["moves"]) == ("program_counter",
                                             "states_per_s")
        assert layer in layers      # a layer the manifest already names
        assert lib.NAME_RE.match(name) and lib.UNIT_RE.match(unit)
        assert os.path.isfile(os.path.join(lib.BENCH, "layers",
                                           name + ".py"))
    for cell in CELLS:
        names = {m["name"] for m in lib.resolve(cell)["per_layer"]}
        assert set(NEW) <= names, cell


def _run(counters=({}, {}), gauges=None, searches=4):
    a, b = counters
    return {"out": {"trace_dir": None, "artifacts": {
        "searches": searches,
        "at_window": {"counters": a, "gauges": {}},
        "after": {"counters": b, "gauges": gauges or {}}}},
        "trace": None}


def test_nothing_to_read_is_none_never_zero():
    """The program as the parent has it: its counters and gauges, none of
    the five."""
    bare = _run(({"search.seed_bytes": 8, "compile.xla_compile_s": 1.5},
                 {"search.seed_bytes": 40, "compile.xla_compile_s": 1.5}),
                {"search.table_bytes": 99, "profile.status": "loaded"})
    for name in NEW:
        assert _read(name, bare) is None, name
        assert _read(name, {"out": None}) is None, name
        assert _read(name, {"out": {"artifacts": {}}}) is None, name
    recorded = ({"seed.keys_s": 1.0, "seed.upload_s": 1.0,
                 "dispatch.launch_s": 1.0},) * 2
    for name in ("seed_build_s", "seed_upload_s", "launch_s"):
        assert _read(name, _run(recorded, searches=0)) is None, name


def test_the_arithmetic_on_a_recorded_run():
    """One warm-up search before the window and four inside it: the rise
    of each float counter over the searches; the gauges as they stand at
    the window's end, in MB of 10^6 bytes."""
    at = {"seed.keys_s": 0.5, "seed.tables_s": 0.25, "seed.upload_s": 0.125,
          "dispatch.launch_s": 60.0}
    after = {"seed.keys_s": 0.5 + 4 * 0.01, "seed.tables_s": 0.25 + 4 * 0.07,
             "seed.upload_s": 0.125 + 4 * 0.09,
             "dispatch.launch_s": 60.0 + 4 * 0.002}
    run = _run((at, after), {"program.temp_bytes": 1_052_300_000,
                             "program.hbm_bytes": 1_622_700_000})
    assert abs(_read("seed_build_s", run) - 0.08) < 1e-12
    assert abs(_read("seed_upload_s", run) - 0.09) < 1e-12
    assert abs(_read("launch_s", run) - 0.002) < 1e-12
    assert _read("program_temp_mb", run) == 1052.3
    assert _read("program_hbm_mb", run) == 1622.7
    # a capped search builds no table on the host: one of the two counters
    # is enough to read, the other counts as not risen
    only_keys = _run(({"seed.keys_s": 1.0}, {"seed.keys_s": 1.5}))
    assert _read("seed_build_s", only_keys) == 0.125
    # a program without temporaries reads 0, which is a number
    assert _read("program_temp_mb",
                 _run(gauges={"program.temp_bytes": 0})) == 0.0


def test_traced_rehearsal_reads_all_five():
    p = subprocess.run(
        [sys.executable, os.path.join(lib.BENCH, "run.py"), "--workload",
         "desk-recheck-3p", "--seed", "2147484034", "--seconds", "1",
         "--trace", "1", "--rehearse-on-cpu"],
        cwd=lib.ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", JAXMC_COMPILE_CACHE="off"))
    assert p.returncode == 0, p.stderr[-2000:]
    assert "NOT a chip run" in p.stdout and "correct=True" in p.stdout
    for line in p.stdout.splitlines():
        try:
            assert "correct" not in json.loads(line)
        except ValueError:
            pass
    got = {}
    for name in NEW:
        (line,) = [ln for ln in p.stdout.splitlines()
                   if ln.startswith(f"bench: {name} = ")]
        got[name] = float(line.split(" = ")[1].split()[0])
    assert all(v > 0 for v in got.values()), got
    assert got["program_hbm_mb"] > got["program_temp_mb"]
