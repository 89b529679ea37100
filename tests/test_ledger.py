r"""Persistent run ledger (ISSUE 17, jaxmc/obs/ledger.py): append /
flock-concurrency / torn-line tolerance, artifact backfill over
BENCH_r*-shaped records and `--metrics-out` artifacts, trajectory
rendering via `python -m jaxmc.obs history`, and the --fail-on-regress
gate firing (exit 1) on a synthesized degraded run.

Pure stdlib + tmp ledgers throughout — conftest pins JAXMC_LEDGER=off
so nothing here (or anywhere in the suite) touches ~/.cache/jaxmc.
"""

import io
import json
import os
import threading

import pytest

from jaxmc.obs import ledger
from jaxmc.obs.report import main as obs_main

pytestmark = pytest.mark.obs


def mk_summary(rate=5000.0, ts=1000.0, platform="cpu", env=None):
    """A minimal jaxmc.metrics summary with a computable states/sec."""
    wall = 2.0
    return {
        "schema": "jaxmc.metrics/4", "started_at": ts,
        "phases": [{"name": "search", "wall_s": wall}],
        "counters": {}, "gauges": {}, "levels": [],
        "env": dict({"platform": platform}, **(env or {})),
        "result": {"ok": True, "generated": int(rate * wall),
                   "distinct": 10, "diameter": 3, "truncated": False,
                   "wall_s": wall},
    }


class TestPathResolution:
    def test_env_off_values_disable(self, monkeypatch):
        for v in ("off", "0", "no", "NONE", " disabled "):
            monkeypatch.setenv("JAXMC_LEDGER", v)
            assert ledger.ledger_path() is None
        monkeypatch.setenv("JAXMC_LEDGER", "/tmp/x.jsonl")
        assert ledger.ledger_path() == "/tmp/x.jsonl"
        # explicit arg beats the env
        assert ledger.ledger_path("/tmp/y.jsonl") == "/tmp/y.jsonl"

    def test_append_summary_disabled_returns_false(self, monkeypatch):
        monkeypatch.setenv("JAXMC_LEDGER", "off")
        assert ledger.append_summary(mk_summary()) is False


class TestAppendRead:
    def test_roundtrip_and_rung_derivation(self, tmp_path):
        lp = str(tmp_path / "ledger.jsonl")
        assert ledger.append_summary(
            mk_summary(rate=4000.0), source="/x/warm_leg.json",
            path=lp) is True
        (e,) = ledger.read_entries(lp)
        assert e["rung"] == "warm_leg"
        assert e["states_per_sec"] == pytest.approx(4000.0)
        assert e["platform"] == "cpu" and e["id"]

    def test_no_rate_no_entry(self, tmp_path):
        lp = str(tmp_path / "ledger.jsonl")
        s = mk_summary()
        del s["result"]  # trace-only / failed run: no trajectory point
        assert ledger.append_summary(s, path=lp) is False
        assert not os.path.exists(lp)

    def test_torn_tail_and_duplicate_ids_tolerated(self, tmp_path):
        lp = str(tmp_path / "ledger.jsonl")
        e = ledger.make_entry("r", 100.0, 1.0)
        ledger.append_entries([e, e], lp)  # same content twice
        with open(lp, "a") as fh:
            fh.write('{"rung": "torn", "states_per_')  # crashed writer
        ents = ledger.read_entries(lp)
        assert len(ents) == 1 and ents[0]["rung"] == "r"

    def test_concurrent_appends_no_torn_lines(self, tmp_path):
        lp = str(tmp_path / "ledger.jsonl")
        n_threads, per = 8, 25

        def worker(k):
            for i in range(per):
                ledger.append_entries(
                    [ledger.make_entry(f"t{k}", 1.0 * i, float(i))],
                    lp)

        ts = [threading.Thread(target=worker, args=(k,))
              for k in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        with open(lp) as fh:
            lines = [ln for ln in fh if ln.strip()]
        assert len(lines) == n_threads * per
        for ln in lines:
            json.loads(ln)  # every line parses: no interleaving
        assert len(ledger.read_entries(lp)) == n_threads * per


class TestBackfill:
    def test_import_committed_history_idempotent(self, tmp_path):
        lp = str(tmp_path / "ledger.jsonl")
        # a driver bench record (the family's shape; the committed
        # BENCH_r01-r05 files went with bench.py) and a dead one
        (tmp_path / "BENCH_r02.json").write_text(json.dumps({
            "n": 2, "rc": 0, "parsed": {
                "metric": "states/sec, exhaustive (platform=cpu)",
                "value": 16638.7, "unit": "states/sec",
                "vs_baseline": 2.694}}))
        (tmp_path / "BENCH_r03.json").write_text(json.dumps({
            "n": 3, "rc": 124, "parsed": None}))
        # ... and a `--metrics-out` artifact, in the shape a run writes
        (tmp_path / "run_a.json").write_text(json.dumps(mk_summary()))
        pats = [str(tmp_path / "BENCH_r*.json"),
                str(tmp_path / "run_*.json")]
        skipped = []
        n = ledger.import_artifacts(pats, lp, skipped=skipped)
        assert n == 2
        ents = ledger.read_entries(lp)
        assert len(ents) == n
        # bench runs land on the shared "bench" rung, a metrics artifact
        # on the rung its file is named for, at generated / wall
        by_rung = {e["rung"]: e for e in ents}
        assert set(by_rung) == {"bench", "run_a"}
        assert by_rung["run_a"]["kind"] == "metrics"
        assert by_rung["run_a"]["states_per_sec"] == 5000.0
        assert by_rung["run_a"]["platform"] == "cpu"
        # dead bench runs are recorded as skips, never import failures
        assert len(skipped) == 1 and "BENCH_r03" in skipped[0]
        # content addressing: the same import is a no-op
        assert ledger.import_artifacts(pats, lp) == 0
        assert len(ledger.read_entries(lp)) == n

    def test_unparseable_artifact_skips_not_raises(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text('{"schema": "nope"}')
        lp = str(tmp_path / "ledger.jsonl")
        skipped = []
        assert ledger.import_artifacts([str(bad)], lp,
                                       skipped=skipped) == 0
        assert len(skipped) == 1 and "bad.json" in skipped[0]


class TestTrajectoryFlags:
    def rows(self, rates, rung="r"):
        return [ledger.make_entry(rung, v, float(i), run=f"run{i}")
                for i, v in enumerate(rates)]

    def test_latest_only_is_judged(self):
        # a historical dip that later runs recovered from must NOT flag
        assert ledger.flag_latest(self.rows([100, 20, 110]),
                                  25.0, 5) is None
        flag = ledger.flag_latest(self.rows([100, 110, 20]), 25.0, 5)
        assert flag and flag.startswith("REGRESS")
        assert "run2" in flag and "-81.8%" in flag

    def test_window_bounds_the_reference(self):
        # the 1000 is outside the 2-run window: no flag vs best-of-2
        assert ledger.flag_latest(self.rows([1000, 90, 100, 95]),
                                  25.0, 2) is None

    def test_env_change_attribution_rides_the_flag(self):
        rows = self.rows([100, 100])
        rows[0]["env"] = {"jax_version": "0.4.1", "platform": "cpu"}
        rows[-1]["env"] = {"jax_version": "0.5.0", "platform": "cpu"}
        rows[-1]["states_per_sec"] = 10.0
        flag = ledger.flag_latest(rows, 25.0, 5)
        assert "env changed" in flag
        assert "jax_version: 0.4.1 -> 0.5.0" in flag


class TestHistoryCli:
    def _seed(self, tmp_path, rates):
        lp = str(tmp_path / "ledger.jsonl")
        ledger.append_entries(
            [ledger.make_entry("warm_leg", v, float(i), run=f"r{i:02d}")
             for i, v in enumerate(rates)], lp)
        return lp

    def test_renders_trajectory_table(self, tmp_path):
        lp = self._seed(tmp_path, [4000, 4400, 4200])
        buf = io.StringIO()
        rc = obs_main(["history", "--ledger", lp], out=buf)
        out = buf.getvalue()
        assert rc == 0
        assert "warm_leg" in out
        assert "4,000 -> 4,400 -> 4,200" in out
        assert "no regressions flagged" in out

    def test_fail_on_regress_exit_1_on_degraded_run(self, tmp_path):
        lp = self._seed(tmp_path, [4000, 4400, 1000])
        buf = io.StringIO()
        rc = obs_main(["history", "--ledger", lp,
                       "--fail-on-regress"], out=buf)
        assert rc == 1
        assert "REGRESS states/sec warm_leg" in buf.getvalue()
        # without the gate flag the same history renders rc 0
        assert obs_main(["history", "--ledger", lp],
                        out=io.StringIO()) == 0

    def test_import_then_render_one_invocation(self, tmp_path):
        art = tmp_path / "warm_leg.json"
        art.write_text(json.dumps(mk_summary(rate=3000.0)))
        lp = str(tmp_path / "ledger.jsonl")
        buf = io.StringIO()
        rc = obs_main(["history", "--ledger", lp,
                       "--import", str(art)], out=buf)
        out = buf.getvalue()
        assert rc == 0
        assert "imported 1 new entry" in out
        assert "warm_leg" in out and "3,000" in out

    def test_rung_filter(self, tmp_path):
        lp = str(tmp_path / "ledger.jsonl")
        ledger.append_entries([ledger.make_entry("a", 1.0, 1.0),
                               ledger.make_entry("b", 2.0, 1.0)], lp)
        buf = io.StringIO()
        assert obs_main(["history", "--ledger", lp, "--rung", "a"],
                        out=buf) == 0
        out = buf.getvalue()
        assert "a" in out and "\n  b " not in out
