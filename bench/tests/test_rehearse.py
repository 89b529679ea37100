"""The harness end to end on XLA:CPU at toy size: no result object, ever;
and `correct` comes out false when the timed path is broken underneath."""

import json
import os
import subprocess
import sys

import pytest

import lib

CELLS = [w["name"] for w in lib.load_json(
    os.path.join(lib.ROOT, "BENCHMARK.json"))["workloads"]]


def _run(args, **env):
    return subprocess.run(
        [sys.executable, os.path.join(lib.BENCH, "run.py")] + args,
        cwd=lib.ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAXMC_COMPILE_CACHE="off", **env))


def _has_result_line(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except ValueError:
            pass
    return False


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_ends_without_a_result_object(cell, trace):
    p = _run(["--workload", cell, "--seed", "2147483999", "--seconds", "1",
              "--trace", trace, "--rehearse-on-cpu"])
    assert p.returncode == 0, p.stderr[-2000:]
    assert "NOT a chip run" in p.stdout
    assert "correct=True" in p.stdout
    assert not _has_result_line(p.stdout)


@pytest.mark.parametrize("cell", ["desk-recheck-3p", "desk-default-3p"])
def test_no_chip_no_result(cell):
    """Without --rehearse-on-cpu, on a machine whose jax has no TPU."""
    p = _run(["--workload", cell, "--seed", "1", "--seconds", "1",
              "--trace", "0"])
    assert p.returncode != 0
    assert not _has_result_line(p.stdout)
    assert "no result" in p.stderr


def test_alone_in_a_directory_no_result(tmp_path):
    import shutil
    shutil.copytree(lib.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(lib.ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "desk-recheck-3p", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and not _has_result_line(p.stdout)


def test_broken_timed_path_comes_out_not_correct(monkeypatch, capsys):
    """Drive the recheck driver itself (the rest of a run, past the look for
    a chip) with an answer altered where it is produced: from the second
    search on, the engine loses one distinct state."""
    monkeypatch.setenv("JAXMC_COMPILE_CACHE", "off")
    import time
    sys.path.insert(0, lib.ROOT)
    from jaxmc.session import CheckSession
    real = CheckSession.explore
    calls = {"n": 0}

    def lossy(self, *a, **kw):
        res = real(self, *a, **kw)
        calls["n"] += 1
        if calls["n"] >= 3:
            res.distinct -= 1
        return res

    monkeypatch.setattr(CheckSession, "explore", lossy)
    res = lib.resolve("desk-recheck-3p")
    driver = lib.load_module(res["driver_path"], "bench_driver_broken")
    out = driver.run(dict(res, seed=5, seconds=1.5, trace=False,
                          rehearsal=True, t0=time.time()))
    assert out["attempted"] >= 3
    assert out["failed"] == out["attempted"] - 1
    assert out["correct"] is False
    assert "FAILED" in capsys.readouterr().out
