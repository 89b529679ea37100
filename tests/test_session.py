r"""CheckSession (jaxmc/session.py): the resumable parse -> compile ->
explore session core under the `check` CLI and the serve daemon.

Pins the ISSUE 7 refactor contract:
  - stage-by-stage results match the engines driven directly (the
    byte-identical-CLI guarantee reduces to this: cli.py renders the
    same CheckResult the engines always produced);
  - stages are ordered, idempotent, and auto-chain;
  - a session resumes mid-search from a checkpoint (truncate -> resume
    parity) and replays a COMPLETED run's final checkpoint instantly;
  - cooperative drain (jaxmc/drain.py): the engine checkpoints at a
    safe boundary, flags the result drained, and the CLI exits 143
    with spans closed — the graceful-shutdown satellite.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from jaxmc import drain, obs
from jaxmc.engine.explore import Explorer
from jaxmc.session import CheckSession, SessionConfig, load_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = os.path.join(REPO, "specs")


def spec(name):
    return os.path.join(SPECS, f"{name}.tla")


def session(name, **kw):
    return CheckSession(SessionConfig(spec=spec(name), **kw))


@pytest.fixture(autouse=True)
def _clean_drain():
    drain.clear()
    yield
    drain.clear()


class TestStages:
    def test_stage_order_and_idempotence(self):
        s = session("constoy", workers=1)
        assert s.stage is None
        assert s.parse() == "model"
        assert s.stage == "parse"
        assert s.parse() == "model"  # idempotent
        s.compile()
        assert s.stage == "compile"
        eng = s.engine
        s.compile()  # idempotent: same engine object
        assert s.engine is eng
        res = s.explore()
        assert s.stage == "explore" and res.ok

    def test_explore_auto_chains(self):
        s = session("constoy", workers=1)
        res = s.explore()  # parse+compile implicitly
        assert res.ok and s.stage == "explore"

    @pytest.mark.parametrize("name", ["viewtoy", "symtoy", "constoy"])
    def test_parity_with_direct_engine(self, name):
        # the session must produce exactly the CheckResult the serial
        # engine produces — counts, verdict, violation identity
        direct = Explorer(load_model(spec(name), None, False)).run()
        res = session(name, workers=1).explore()
        assert (res.ok, res.distinct, res.generated, res.diameter) == \
            (direct.ok, direct.distinct, direct.generated,
             direct.diameter)
        if direct.violation is not None:
            assert (res.violation.kind, res.violation.name) == \
                (direct.violation.kind, direct.violation.name)
            assert [st for st, _ in res.violation.trace] == \
                [st for st, _ in direct.violation.trace]

    def test_assumes_mode(self, tmp_path, capsys):
        sp = tmp_path / "AsmToy.tla"
        sp.write_text("---- MODULE AsmToy ----\n"
                      "ASSUME 1 + 1 = 2\n"
                      "====\n")
        (tmp_path / "AsmToy.cfg").write_text("\n")
        s = CheckSession(SessionConfig(spec=str(sp)))
        assert s.parse() == "assumes"
        rc = s.run_assumes()
        out = capsys.readouterr().out
        assert rc == 0 and "1 assumption checked" in out

    def test_describe_carries_identity(self):
        s = session("constoy", workers=1)
        s.explore()
        d = s.describe()
        assert d["stage"] == "explore"
        assert d["module"] == "constoy"
        assert d["backend"] == "interp"


class TestResume:
    def test_resume_mid_search(self, tmp_path):
        # truncate at a state limit (writes a checkpoint), then a FRESH
        # session resumes and completes with the uninterrupted totals
        ck = str(tmp_path / "mid.ck")
        full = session("constoy", workers=1).explore()
        part = session("constoy", workers=1, max_states=5,
                       checkpoint=ck).explore()
        assert part.truncated and os.path.exists(ck)
        res = session("constoy", workers=1, resume=ck).explore()
        assert not res.truncated
        assert (res.distinct, res.generated) == \
            (full.distinct, full.generated)

    def test_final_checkpoint_replay(self, tmp_path):
        # final_checkpoint persists a COMPLETED run; resuming it (the
        # serve warm path) replays the same totals over an empty queue
        ck = str(tmp_path / "final.ck")
        s = session("constoy", workers=1, checkpoint=ck,
                    final_checkpoint=True)
        res1 = s.explore()
        assert res1.ok and os.path.exists(ck)
        res2 = s.explore(resume_from=ck)  # warm re-run, same session
        assert (res2.ok, res2.distinct, res2.generated) == \
            (res1.ok, res1.distinct, res1.generated)
        res3 = session("constoy", workers=1, resume=ck).explore()
        assert (res3.distinct, res3.generated) == \
            (res1.distinct, res1.generated)

    def test_jax_session_stamps_layout_sig(self, tmp_path):
        ck = str(tmp_path / "res.ck")
        s = session("constoy", backend="jax", platform="cpu",
                    resident=True, no_trace=True, checkpoint=ck,
                    final_checkpoint=True)
        res = s.explore()
        assert res.ok and s.layout_sig and os.path.exists(ck)
        # warm replay through the SAME engine: zero dispatches, same
        # counts — the serve daemon's warm-hit path
        tel = obs.Telemetry()
        with obs.use_local(tel):
            res2 = s.explore(resume_from=ck)
        assert (res2.distinct, res2.generated) == \
            (res.distinct, res.generated)
        assert sum(1 for lv in tel.levels
                   if lv.get("fresh_compile")) == 0


class TestDrain:
    def test_drained_result_checkpoints(self, tmp_path):
        ck = str(tmp_path / "drain.ck")
        drain.request("unit test")
        res = session("constoy", workers=1, checkpoint=ck).explore()
        assert res.drained and res.truncated and res.ok
        assert any("drained" in w for w in res.warnings)
        assert os.path.exists(ck)
        drain.clear()
        full = session("constoy", workers=1).explore()
        res2 = session("constoy", workers=1, resume=ck).explore()
        assert (res2.distinct, res2.generated) == \
            (full.distinct, full.generated)

    def test_drain_without_checkpoint_warns(self):
        drain.request("unit test")
        res = session("constoy", workers=1).explore()
        assert res.drained
        assert any("no checkpoint was configured" in w
                   for w in res.warnings)

    def test_sigterm_drains_cli_with_named_exit(self, tmp_path):
        # the graceful-shutdown satellite end to end: SIGTERM mid-search
        # -> checkpoint + named reason + exit 143 + NO open spans in the
        # trace; a resume then reproduces the uninterrupted counts
        ck = str(tmp_path / "cli.ck")
        tr = str(tmp_path / "cli.jsonl")
        limit = 30000
        p = subprocess.Popen(
            [sys.executable, "-m", "jaxmc", "check",
             spec("transfer_scaled"), "--workers", "1",
             "--max-states", str(limit), "--checkpoint", ck,
             "--trace", tr, "--quiet"],
            cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"))
        time.sleep(2.5)  # well inside the ~6s search
        p.send_signal(signal.SIGTERM)
        out, err = p.communicate(timeout=60)
        assert p.returncode == 143, (p.returncode, out, err)
        assert "drained" in err and "SIGTERM" in err
        assert os.path.exists(ck)
        events = [json.loads(ln) for ln in open(tr)]
        opens = sum(1 for e in events if e["ev"] == "span_open")
        closes = sum(1 for e in events if e["ev"] == "span")
        assert opens == closes, "drained run left open spans"
        assert any(e["ev"] == "run_end" for e in events)
        # resume completes with the totals of an uninterrupted run
        expect = session("transfer_scaled", workers=1,
                         max_states=limit).explore()
        res = session("transfer_scaled", workers=1, max_states=limit,
                      resume=ck).explore()
        assert (res.distinct, res.generated) == \
            (expect.distinct, expect.generated)


class TestFusedGroups:
    """ISSUE 7 satellite: the JAXMC_FUSED_MAX_INSTANCES ceiling no
    longer drops many-instance models to one-dispatch-per-ACTION on
    CPU — actions split into fused ARM GROUPS of <= the cap, counts
    identical."""

    @pytest.mark.parametrize("name", ["constoy", "viewtoy"])
    def test_grouped_counts_match_interp(self, name, monkeypatch):
        from jaxmc.backend.bfs import TpuExplorer
        # cap 1 instance per fused group: every action becomes its own
        # fused group, the maximal split — counts must not move
        monkeypatch.setenv("JAXMC_FUSED_MAX_INSTANCES", "1")
        model = load_model(spec(name), None, False)
        direct = Explorer(load_model(spec(name), None, False)).run()
        tel = obs.Telemetry()
        with obs.use_local(tel):
            res = TpuExplorer(model, host_seen=True,
                              store_trace=False).run()
        assert (res.ok, res.distinct, res.generated) == \
            (direct.ok, direct.distinct, direct.generated)
        # the grouped path actually ran: more than one group at cap 1
        assert tel.gauges.get("expand.fused_groups", 0) >= 2
