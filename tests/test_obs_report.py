r"""`python -m jaxmc.obs` report/diff tests: artifact normalization,
the trajectory table, regression flags (seeded throughput drop, phase
blowup, backend demotion), --fail-on-regress gating, and the subprocess
smoke test that guards the entrypoint against import rot.

Tier-1 fast: fixture artifacts are built with a fake-clock Telemetry
(no jax); the one real run is an interp check on the symtoy micro model.
"""

import io
import json
import os
import subprocess
import sys

import pytest

from jaxmc import obs
from jaxmc.obs import report

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = os.path.join(REPO, "specs")


def mk_artifact(path, rate, platform, phases, jax_version="0.4.37",
                generated=100000):
    """A minimal-but-valid jaxmc.metrics/2 check artifact: `generated`
    states over generated/rate seconds, the given phase walls."""
    clk = {"t": 1000.0}
    tel = obs.Telemetry(clock=lambda: clk["t"])
    for name, wall in phases.items():
        h = tel.span(name)
        h.__enter__()
        clk["t"] += wall
        h.done()
    tel.level(0, frontier=1, generated=generated, wall_s=sum(
        phases.values()))
    tel.set_meta(backend="jax" if platform != "interp" else "interp",
                 spec="specs/symtoy.tla",
                 env={"jax_version": jax_version, "platform":
                      None if platform == "interp" else platform,
                      "device_count":
                      None if platform == "interp" else 1})
    tel.write_metrics(str(path), result={
        "ok": True, "distinct": generated // 2, "generated": generated,
        "diameter": 10, "truncated": False,
        "wall_s": generated / rate})
    with open(path) as fh:
        obs.validate_summary(json.load(fh), check_run=True)
    return str(path)


def mk_bench(path, n, value, metric):
    with open(path, "w") as fh:
        json.dump({"n": n, "cmd": "python bench.py", "rc": 0,
                   "parsed": {"metric": metric, "value": value,
                              "unit": "states/sec", "vs_baseline": 1.0,
                              "vs_tlc_estimate": 0.5}}, fh)
    return str(path)


def run_cli(argv):
    out = io.StringIO()
    rc = report.main(argv, out=out)
    return rc, out.getvalue()


class TestReport:
    def test_report_renders_phases_and_result(self, tmp_path):
        p = mk_artifact(tmp_path / "a.json", rate=5000.0, platform="tpu",
                        phases={"load": 0.5, "device_init": 12.0,
                                "search": 7.5})
        rc, out = run_cli(["report", p])
        assert rc == 0
        assert "device_init" in out and "search" in out
        assert "ok=True" in out and "generated=100000" in out
        assert "5,000" in out  # states/sec

    def test_report_unreadable_exits_2(self, tmp_path):
        bad = tmp_path / "nope.json"
        bad.write_text("{\"hello\": 1}")
        assert report.main(["report", str(bad)]) == 2
        assert report.main(["report", str(tmp_path / "missing.json")]) == 2


class TestDiff:
    def seeded(self, tmp_path):
        good = mk_artifact(tmp_path / "r1.json", rate=8000.0,
                           platform="tpu",
                           phases={"device_init": 2.0, "search": 10.0})
        bad = mk_artifact(tmp_path / "r2.json", rate=900.0,
                          platform="interp",
                          phases={"device_init": 95.0, "search": 10.5},
                          jax_version="0.5.0")
        return good, bad

    def test_seeded_regression_is_flagged(self, tmp_path):
        good, bad = self.seeded(tmp_path)
        rc, out = run_cli(["diff", good, bad])
        assert rc == 0  # informational without --fail-on-regress
        assert "REGRESS states/sec" in out
        assert "REGRESS backend demotion" in out and "tpu -> interp" in out
        assert "REGRESS phase device_init" in out
        # the env-change note attributes it (jax upgrade in the fixture)
        assert "jax_version: 0.4.37 -> 0.5.0" in out

    def test_fail_on_regress_gates_exit_code(self, tmp_path):
        good, bad = self.seeded(tmp_path)
        rc, _ = run_cli(["diff", good, bad, "--fail-on-regress"])
        assert rc == 1
        # reversed order is an improvement: exit 0
        rc, out = run_cli(["diff", bad, good, "--fail-on-regress"])
        assert rc == 0
        # self-diff: no flags
        rc, out = run_cli(["diff", good, good, "--fail-on-regress"])
        assert rc == 0 and "no regressions flagged" in out

    def test_bench_family_demotion(self, tmp_path):
        b4 = mk_bench(tmp_path / "BENCH_r04.json", 4, 1729.6,
                      "states/sec, exhaustive raft (... COMPLETED, "
                      "platform=cpu, device-resident BFS)")
        b5 = mk_bench(tmp_path / "BENCH_r05.json", 5, 6204.1,
                      "states/sec, exhaustive raft (... COMPLETED, "
                      "EXACT PYTHON INTERPRETER ONLY ...)")
        rc, out = run_cli(["diff", b4, b5, "--fail-on-regress"])
        assert rc == 1
        assert "REGRESS backend demotion r04 -> r05" in out
        assert "cpu -> interp" in out

    def test_mixed_kinds_and_three_way(self, tmp_path):
        a = mk_artifact(tmp_path / "a.json", rate=4000.0, platform="cpu",
                        phases={"search": 5.0})
        b = mk_bench(tmp_path / "b.json", 7, 4100.0,
                     "raft (... platform=cpu ...)")
        c = mk_artifact(tmp_path / "c.json", rate=4500.0, platform="cpu",
                        phases={"search": 4.0})
        rc, out = run_cli(["diff", a, b, c])
        assert rc == 0
        for label in ("a", "r07", "c"):
            assert label in out

    def test_metrics_regress_attributes_platform_swap(self, tmp_path):
        # same attribution on plain metrics artifacts whose env block
        # predates the platform field (env.platform None, platform
        # resolved from gauges): the swap must surface in the note
        good = mk_artifact(tmp_path / "g.json", rate=9000.0,
                           platform="tpu", phases={"search": 3.0})
        bad = mk_artifact(tmp_path / "b.json", rate=900.0,
                          platform="interp", phases={"search": 3.0})
        for p, plat in ((good, "tpu"), (bad, None)):
            obj = json.load(open(p))
            obj["env"]["platform"] = None
            if plat:
                obj.setdefault("gauges", {})["device.platform"] = plat
            json.dump(obj, open(p, "w"))
        rc, out = run_cli(["diff", good, bad])
        assert "REGRESS backend demotion" in out
        assert "environment changed" in out
        assert "platform: tpu -> interp" in out

    def test_diff_needs_two(self, tmp_path):
        a = mk_artifact(tmp_path / "a.json", rate=1000.0,
                        platform="cpu", phases={"search": 1.0})
        assert report.main(["diff", a]) == 2


class TestOracleHighlights:
    """The preflight oracle's verdict gauges (ISSUE 11 satellite)
    surface in `obs report` highlights: the chosen platform, the
    preflight wall, and one cell per candidate probe."""

    def art(self, tmp_path):
        clk = {"t": 1000.0}
        tel = obs.Telemetry(clock=lambda: clk["t"])
        with tel.span("search"):
            clk["t"] += 2.0
        tel.level(0, frontier=1, generated=1000, wall_s=2.0)
        tel.gauge("backend.oracle_choice", "cpu")
        tel.gauge("backend.oracle_wall_s", 1.23)
        tel.gauge("backend.oracle_probe", {
            "tpu": {"live": False,
                    "error": "probe wedged past 7.0s (device hung at init?)"},
            "cpu": {"live": True, "devices": 1, "compile_s": 0.4,
                    "dispatch_s": 0.012}})
        tel.set_meta(backend="jax", spec="specs/symtoy.tla",
                     env={"jax_version": "0.4.37", "platform": "cpu",
                          "device_count": 1})
        p = tmp_path / "oracle.json"
        tel.write_metrics(str(p), result={
            "ok": True, "distinct": 500, "generated": 1000,
            "diameter": 3, "truncated": False, "wall_s": 2.0})
        return str(p)

    def test_verdict_and_probe_walls_in_highlights(self, tmp_path):
        rc, out = run_cli(["report", self.art(tmp_path)])
        assert rc == 0
        assert "backend.oracle_choice=cpu" in out
        assert "backend.oracle_wall_s=1.23" in out
        assert "cpu=0.012s" in out
        assert "tpu=dead(probe wedged past 7.0s" in out


class TestEntrypointSmoke:
    """Guards `python -m jaxmc.obs` against import rot: a real interp
    run's artifact must render with exit 0 and a non-empty phase table
    through the actual module entrypoint (fresh interpreter)."""

    def test_report_subprocess_on_real_artifact(self, tmp_path):
        from jaxmc.cli import main as cli_main
        art = tmp_path / "interp.metrics.json"
        rc = cli_main(["check", os.path.join(SPECS, "symtoy.tla"),
                       "--cfg", os.path.join(SPECS, "symtoy.cfg"),
                       "--no-deadlock", "--quiet",
                       "--metrics-out", str(art)])
        assert rc == 0 and art.exists()
        r = subprocess.run(
            [sys.executable, "-m", "jaxmc.obs", "report", str(art)],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "phases:" in r.stdout
        # non-empty table: the interp pipeline's phases all render
        for phase in ("load", "search"):
            assert phase in r.stdout, r.stdout

    def test_diff_subprocess_exit_codes(self, tmp_path):
        good = mk_artifact(tmp_path / "g.json", rate=9000.0,
                           platform="tpu", phases={"search": 3.0})
        bad = mk_artifact(tmp_path / "b.json", rate=100.0,
                          platform="interp", phases={"search": 3.0})
        r = subprocess.run(
            [sys.executable, "-m", "jaxmc.obs", "diff", good, bad,
             "--fail-on-regress"],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert r.returncode == 1, r.stdout + r.stderr
        assert "states/sec" in r.stdout


# ------------------------------------------------------- obs timeline

def _trace_file(path, psid, parent, pid, events=(), t0=1000.0,
                command="check"):
    """A synthetic PR-16 trace file: proc_meta header + events."""
    lines = [{"ev": "proc_meta", "t": t0, "mono": 1.0, "pid": pid,
              "argv": ["jaxmc"], "psid": psid, "parent_span": parent,
              "env": {}, "tid": "t" * 16},
             {"ev": "run_start", "t": t0,
              "meta": {"command": command}, "tid": "t" * 16}]
    lines += list(events)
    with open(path, "w") as fh:
        for ln in lines:
            fh.write(json.dumps(ln) + "\n")
    return str(path)


class TestTimeline:
    def run_timeline(self, files, extra=()):
        buf = io.StringIO()
        rc = report.main(["timeline"] + list(extra) + list(files),
                         out=buf)
        return rc, buf.getvalue()

    def test_stitches_parent_child_and_workers(self, tmp_path):
        parent = _trace_file(
            tmp_path / "daemon.jsonl", "p" * 16, None, 100,
            events=[{"ev": "parallel.worker_span", "t": 1001.0,
                     "pid": 201, "span": "w" * 16,
                     "parent": "p" * 16, "level": 1, "tid": "t" * 16}])
        child = _trace_file(tmp_path / "job.jsonl", "c" * 16,
                            "p" * 16, 150, t0=1000.5, command="serve")
        rc, out = self.run_timeline([parent, child])
        assert rc == 0
        assert "summary: files=2 processes=3 lanes=3 events=5 " \
               "orphans=0 gaps=0" in out
        assert "parent=P0" in out       # child + worker parented
        assert "ORPHAN" not in out

    def test_orphan_flagged_and_gates(self, tmp_path):
        lost = _trace_file(tmp_path / "lost.jsonl", "c" * 16,
                           "f" * 16, 150)  # parent span in no file
        rc, out = self.run_timeline([lost])
        assert rc == 0                  # informational without the flag
        assert "orphans=1" in out and "ORPHAN" in out
        rc2, out2 = self.run_timeline([lost],
                                      extra=["--fail-on-orphans"])
        assert rc2 == 1

    def test_gap_detection(self, tmp_path):
        f = _trace_file(
            tmp_path / "slow.jsonl", "p" * 16, None, 100,
            events=[{"ev": "log", "t": 1100.0, "msg": "late",
                     "tid": "t" * 16}])
        rc, out = self.run_timeline([f], extra=["--gap-threshold", "30"])
        assert rc == 0
        assert "gaps=1" in out and "silent for" in out

    def test_tolerates_pre_pr16_artifacts_and_torn_lines(self, tmp_path):
        p = tmp_path / "old.jsonl"
        with open(p, "w") as fh:
            fh.write(json.dumps({"ev": "run_start", "t": 1.0,
                                 "meta": {}}) + "\n")
            fh.write('{"ev": "log", "t": 2.0, "msg": "x"}\n')
            fh.write('{"ev": "level", "t": 2.5, "lev')  # torn tail
        rc, out = self.run_timeline([str(p)])
        assert rc == 0
        assert "events=2" in out and "orphans=0" in out

    def test_real_run_timeline_subprocess(self, tmp_path):
        """Entrypoint guard: a real interp run's trace renders through
        `python -m jaxmc.obs timeline` with zero orphans."""
        from jaxmc.cli import main as cli_main
        tr = tmp_path / "run.trace.jsonl"
        rc = cli_main(["check", os.path.join(SPECS, "symtoy.tla"),
                       "--cfg", os.path.join(SPECS, "symtoy.cfg"),
                       "--no-deadlock", "--quiet", "--trace", str(tr)])
        assert rc == 0
        r = subprocess.run(
            [sys.executable, "-m", "jaxmc.obs", "timeline",
             "--fail-on-orphans", str(tr)],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "orphans=0" in r.stdout
        assert "run_start check" in r.stdout
