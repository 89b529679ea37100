"""The parked cell (bench/parked/ci-firstcontact.json): proved on the chip,
left out of BENCHMARK.json for its noise.  Its entries, merged into a copy
of the manifest, resolve to files that exist, and its CPU rehearsal runs end
to end — a cell with a driver kind of its own, added by entries alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import lib


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("parked")
    shutil.copytree(lib.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(lib.ROOT, "jaxmc"), root / "jaxmc")
    bm = lib.load_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
    parked = lib.load_json(os.path.join(lib.BENCH, "parked",
                                        "ci-firstcontact.json"))
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bm[key] = bm[key] + parked[key]
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def test_parked_entries_resolve(tree):
    res = lib.resolve("ci-firstcontact", str(tree / "bench"))
    assert res["mix"]["driver"] == "firstcontact"
    assert os.path.isfile(res["driver_path"])
    assert {m["name"] for m in res["end_to_end"]} == {"firstcontact_s",
                                                      "setup_s"}
    names = {m["name"] for m in res["per_layer"]}
    assert names == {"build_s.ci", "xla_compile_s.ci", "xla_compiles.ci",
                     "serve_overhead_s", "rerun_verdict_s"}
    for name in names:
        assert os.path.isfile(res["reader_path"](name))
        assert lib.NAME_RE.match(name)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_parked_cell_rehearses(tree, trace):
    p = subprocess.run(
        [sys.executable, str(tree / "bench" / "run.py"), "--workload",
         "ci-firstcontact", "--seed", "2147483999", "--trace", trace,
         "--rehearse-on-cpu"], cwd=tree, capture_output=True, text=True,
        timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    assert "correct=True" in p.stdout and "NOT a chip run" in p.stdout
    assert '"correct"' not in p.stdout.splitlines()[-1]
    if trace == "1":
        assert "rerun_verdict_s" in p.stdout


def test_parked_cell_without_a_chip_gives_no_result(tree):
    p = subprocess.run(
        [sys.executable, str(tree / "bench" / "run.py"), "--workload",
         "ci-firstcontact", "--seed", "1", "--trace", "0"], cwd=tree,
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and "no result" in p.stderr
