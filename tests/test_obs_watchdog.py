r"""Watchdog (jaxmc/obs/watchdog.py) tests: heartbeat events, stall
detection on a synthetic wedged span, episode semantics, and the
median-level stall threshold.

Deterministic and tier-1 fast: the per-beat body (`Watchdog._tick`) is
driven directly with a fake clock — no sleeps, no jax; one short
real-thread test pins the daemon wiring.
"""

import json
import time

import pytest

from jaxmc import obs

pytestmark = pytest.mark.obs


class Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def mk(tmp_path, **kw):
    """(telemetry, watchdog, clock, trace_path, stall_msgs)."""
    clk = Clock()
    trace = tmp_path / "trace.jsonl"
    tel = obs.Telemetry(trace_path=str(trace), clock=clk)
    msgs = []
    wd = obs.Watchdog(tel, clock=clk, on_stall=msgs.append,
                      **dict({"interval": 5.0, "stall_factor": 4.0,
                              "min_stall_s": 30.0}, **kw))
    return tel, wd, clk, trace, msgs


def events(trace):
    with open(trace) as fh:
        return [json.loads(ln) for ln in fh if ln.strip()]


class TestHeartbeat:
    def test_heartbeat_event_validates_and_names_open_span(self, tmp_path):
        tel, wd, clk, trace, msgs = mk(tmp_path)
        h = tel.span("device_init", platform="tpu")
        h.__enter__()
        tel.level(0, frontier=3, wall_s=0.5)
        clk.t += 5
        wd._tick(clk.t)
        h.done()
        evs = events(trace)
        for e in evs:
            obs.validate_trace_event(e)
        (hb,) = [e for e in evs if e["ev"] == "heartbeat"]
        assert hb["open_spans"] == ["device_init"]
        assert hb["last_level"] == 0
        assert hb["wall_s"] == 5
        assert hb["progress_seq"] >= 2
        assert hb["rss_bytes"] is None or hb["rss_bytes"] > 0
        assert tel.counters["watchdog.heartbeats"] == 1
        assert not msgs  # 5s of quiet is not a stall

    def test_daemon_thread_beats_for_real(self, tmp_path):
        tel = obs.Telemetry(trace_path=str(tmp_path / "t.jsonl"))
        wd = obs.Watchdog(tel, interval=0.02, min_stall_s=30.0)
        wd.start()
        deadline = time.time() + 2.0
        while time.time() < deadline and \
                tel.counters.get("watchdog.heartbeats", 0) < 2:
            time.sleep(0.02)
        wd.stop()
        tel.close()
        assert tel.counters.get("watchdog.heartbeats", 0) >= 2

    def test_null_telemetry_never_starts(self):
        wd = obs.Watchdog(obs.NullTelemetry())
        assert wd.start() is wd
        assert wd._thread is None
        wd.stop()  # no-op, no crash


class TestStall:
    def test_synthetic_wedged_span_triggers_stall(self, tmp_path):
        tel, wd, clk, trace, msgs = mk(tmp_path)
        h = tel.span("device_init", platform="tpu")
        h.__enter__()
        wd._tick(clk.t)  # latch: the span-open counts as progress
        clk.t += 31      # ... then 31s of silence beats the 30s floor
        wd._tick(clk.t)
        h.done()
        evs = events(trace)
        for e in evs:
            obs.validate_trace_event(e)
        (st,) = [e for e in evs if e["ev"] == "stall"]
        assert st["open_spans"] == ["device_init"]
        assert st["stalled_for_s"] >= 30
        assert st["threshold_s"] == 30
        assert st["last_level"] is None
        assert tel.counters["watchdog.stalls"] == 1
        assert len(msgs) == 1 and "device_init" in msgs[0]

    def test_one_stall_event_per_episode_highwater_tracks(self, tmp_path):
        tel, wd, clk, trace, msgs = mk(tmp_path)
        tel.span("search").__enter__()
        wd._tick(clk.t)  # latch
        clk.t += 31
        wd._tick(clk.t)
        clk.t += 40  # still wedged: no second stall event, deeper water
        wd._tick(clk.t)
        evs = events(trace)
        assert len([e for e in evs if e["ev"] == "stall"]) == 1
        assert len([e for e in evs if e["ev"] == "heartbeat"]) == 3
        assert tel.counters["watchdog.stalls"] == 1
        assert tel.gauges["watchdog.max_stall_s"] >= 71

    def test_progress_ends_episode_and_rearms(self, tmp_path):
        tel, wd, clk, trace, msgs = mk(tmp_path)
        with tel.span("search"):
            wd._tick(clk.t)          # latch
            clk.t += 31
            wd._tick(clk.t)          # episode 1
            tel.level(0, wall_s=1.0)  # progress: episode over
            clk.t += 1
            wd._tick(clk.t)
            assert not wd._stalled
            clk.t += 31              # quiet again: episode 2
            wd._tick(clk.t)
            assert wd._stalled
        evs = events(trace)
        assert len([e for e in evs if e["ev"] == "stall"]) == 2
        assert tel.counters["watchdog.stalls"] == 2

    def test_threshold_follows_median_level_wall(self, tmp_path):
        tel, wd, clk, trace, msgs = mk(tmp_path)
        # fast levels: the 30s floor governs
        assert wd.stall_threshold_s([0.5, 1.0, 2.0]) == 30.0
        # slow levels: factor * median governs (4 * 20 = 80)
        assert wd.stall_threshold_s([10.0, 20.0, 30.0]) == 80.0
        assert wd.stall_threshold_s([]) == 30.0
        # integration: with recorded slow levels a 31s gap is NOT a stall
        for i, w in enumerate((10.0, 20.0, 30.0)):
            tel.level(i, wall_s=w)
        wd._tick(clk.t)  # latch
        clk.t += 31
        wd._tick(clk.t)
        assert "watchdog.stalls" not in tel.counters
        clk.t += 50  # 81s total beats the 80s threshold
        wd._tick(clk.t)
        assert tel.counters["watchdog.stalls"] == 1
        (st,) = [e for e in events(trace) if e["ev"] == "stall"]
        assert st["median_level_s"] == 20.0

    def test_tick_never_raises(self, tmp_path):
        tel, wd, clk, trace, msgs = mk(tmp_path)

        def boom(m):
            raise RuntimeError("stall callback exploded")

        wd.on_stall = boom
        tel.span("search").__enter__()
        wd._tick(clk.t)  # latch
        clk.t += 31
        wd._tick(clk.t)  # callback error swallowed
        assert tel.counters["watchdog.stalls"] == 1

    def test_an_idle_daemon_is_not_a_stall_a_long_owner_run_is(
            self, tmp_path):
        """The serve daemon's fleet recorder (`idle_ok=True`, ISSUE 49): a
        daemon with no span open is waiting for work — the "stall" a
        healthy traced window once counted was that, while the harness
        wrote its trace — and a stall under open job spans names the WAIT
        for the owner apart from the owner's RUN."""
        tel, wd, clk, trace, msgs = mk(tmp_path, idle_ok=True)
        wd._tick(clk.t)
        clk.t += 95                  # no job for a minute and a half
        wd._tick(clk.t)
        assert "watchdog.stalls" not in tel.counters and not msgs
        # the default, a run's recorder, does count quiet between spans
        (tmp_path / "run").mkdir()
        tel2, wd2, clk2, _, msgs2 = mk(tmp_path / "run")
        wd2._tick(clk2.t)
        clk2.t += 31
        wd2._tick(clk2.t)
        assert tel2.counters["watchdog.stalls"] == 1
        assert "no open span" in msgs2[0]
        # two workers, one owner: one job runs, the other waits for it
        import threading
        opened, release = threading.Barrier(3), threading.Event()

        def worker(child):
            with tel.span("job"), tel.span(child):
                opened.wait()
                release.wait()
        threads = [threading.Thread(target=worker, args=(c,))
                   for c in ("job.owner_run", "job.owner_wait")]
        for t in threads:
            t.start()
        opened.wait()
        wd._tick(clk.t)              # latch
        clk.t += 31
        wd._tick(clk.t)
        release.set()
        for t in threads:
            t.join()
        (st,) = [e for e in events(trace) if e["ev"] == "stall"]
        assert sorted(st["open_spans"]) == ["job", "job", "job.owner_run",
                                            "job.owner_wait"]
        assert "job.owner_run" in msgs[0] and "job.owner_wait" in msgs[0]
        clk.t += 31                  # idle again once both are answered
        wd._tick(clk.t)
        assert tel.counters["watchdog.stalls"] == 1
