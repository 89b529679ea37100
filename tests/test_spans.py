r"""Program spans on the profiler's clock, kernel scopes in the lowered
programs, the capacity counters and compile seconds per program (ISSUE 24).

One span system, two sinks: `Telemetry.span()` writes the JSONL event (now
with an `id` and its parent's) AND a `jaxmc.<name>` TraceAnnotation into
the profiler's trace; `backend/bfs.py` opens the spans at the engines'
boundaries and names the kernels with `jax.named_scope`.  None of it may
change a count, a trace, a dispatch or a compile.
"""

import glob
import json
import os
import subprocess
import sys
import threading

import pytest

from jaxmc import obs
from jaxmc.compile.cache import forget_programs
from jaxmc.engine.explore import format_trace
from jaxmc.session import CheckSession, SessionConfig

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = os.path.join(REPO, "specs")
SCOPES = ("jaxmc.expand", "jaxmc.keys", "jaxmc.merge.sort",
          "jaxmc.merge.probe", "jaxmc.merge.scatter", "jaxmc.compact",
          "jaxmc.scan")
ENGINES = {"level": {}, "resident": {"resident": True, "no_trace": True}}


@pytest.fixture(autouse=True)
def _no_capacity_profiles(monkeypatch):
    # capacities from the engines' own defaults, whatever ran before
    monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")


def _session(spec, cfg, engine, tel, **opts):
    return CheckSession(SessionConfig(
        spec=os.path.join(SPECS, spec + ".tla"),
        cfg=os.path.join(SPECS, cfg + ".cfg"), backend="jax",
        platform="cpu", **ENGINES[engine], **opts), tel=tel)


def _events(trace):
    with open(trace) as fh:
        return [json.loads(ln) for ln in fh if ln.strip()]


def _host_spans(trace_dir):
    """(name, start_ns, end_ns) of every `jaxmc.*` host event of the one
    trace under trace_dir."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events
                       if ev.name.startswith("jaxmc."))
    return out


class _Profiled:
    """A jax.profiler session recorded as the benchmark records."""

    def __init__(self, trace_dir):
        self.dir = str(trace_dir)

    def __enter__(self):
        import jax
        opt = jax.profiler.ProfileOptions()
        opt.python_tracer_level = 0
        opt.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opt)
        return self

    def __exit__(self, *a):
        import jax
        jax.profiler.stop_trace()
        return False


class TestSpanIds:
    def test_ids_and_parent_ids_on_both_events(self, tmp_path):
        trace = tmp_path / "t.jsonl"
        tel = obs.Telemetry(trace_path=str(trace))
        with tel.span("search"):
            with tel.span("search.seed"):
                pass
            with tel.span("search.dispatch", maxlvl=4):
                pass
        tel.close()
        evs = [e for e in _events(trace) if e["ev"] in ("span_open", "span")]
        for e in evs:
            obs.validate_trace_event(e)
        opened = {e["name"]: e for e in evs if e["ev"] == "span_open"}
        closed = {e["name"]: e for e in evs if e["ev"] == "span"}
        assert [opened[n]["id"] for n in
                ("search", "search.seed", "search.dispatch")] == [1, 2, 3]
        for name in opened:
            assert closed[name]["id"] == opened[name]["id"]
            assert closed[name]["parent_id"] == opened[name]["parent_id"]
            assert opened[name]["tid"] == closed[name]["tid"]
        assert opened["search"]["parent_id"] is None
        assert opened["search.seed"]["parent_id"] == 1
        assert opened["search.dispatch"]["parent_id"] == 1
        assert opened["search.seed"]["parent"] == "search"  # the name stays

    def test_parents_are_per_thread(self):
        tel = obs.Telemetry()
        inner = {}

        def worker(tag):
            with tel.span("job." + tag) as outer:
                with tel.span("step." + tag) as h:
                    inner[tag] = (outer.id, h.parent_id)

        with tel.span("main") as main:
            ts = [threading.Thread(target=worker, args=(t,))
                  for t in ("a", "b")]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            with tel.span("main.child") as child:
                assert child.parent_id == main.id
        for outer_id, parent_id in inner.values():
            assert parent_id == outer_id      # never the other thread's
        roots = [e for e in tel.recent_events()
                 if e["ev"] == "span_open" and e["name"].startswith("job.")]
        assert [e["parent_id"] for e in roots] == [None, None]
        ids = [e["id"] for e in tel.recent_events()
               if e["ev"] == "span_open"]
        assert sorted(ids) == list(range(1, 7))

    def test_validator_refuses_an_id_that_is_no_int(self):
        ev = {"ev": "span", "name": "x", "t0": 1.0, "wall_s": 0.0}
        obs.validate_trace_event(ev)                      # ids are optional
        obs.validate_trace_event(dict(ev, id=3, parent_id=None))
        with pytest.raises(ValueError, match="parent_id"):
            obs.validate_trace_event(dict(ev, id=3, parent_id="search"))

    def test_null_telemetry_spans_stay_a_noop(self):
        tel = obs.NullTelemetry()
        with tel.span("search.seed", rows=3) as h:
            h.attrs["outcome"] = "ok"    # a throwaway dict
        assert tel.span("x").attrs == {}
        assert tel.recent_events() == []

    def test_obs_and_its_spans_need_no_jax(self):
        code = ("import sys, jaxmc.obs as obs\n"
                "tel = obs.Telemetry()\n"
                "with tel.span('search'):\n"
                "    with tel.span('search.seed'):\n"
                "        pass\n"
                "assert 'jax' not in sys.modules, 'obs imported jax'\n"
                "evs = [e for e in tel.recent_events() if e['ev'] == 'span']\n"
                "assert [e['parent_id'] for e in evs] == [1, None], evs\n")
        r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr


class TestProfilerSink:
    def test_spans_land_in_the_xplane_and_nest_as_the_jsonl_says(
            self, tmp_path):
        pytest.importorskip("jax")
        tel = obs.Telemetry(trace_path=str(tmp_path / "t.jsonl"))
        with _Profiled(tmp_path / "xla") as prof:
            with tel.span("search"):
                with tel.span("search.seed"):
                    pass
                for _ in range(2):
                    with tel.span("search.dispatch"):
                        with tel.span("search.fetch"):
                            pass
        tel.close()
        host = _host_spans(prof.dir)
        assert sorted(n for n, _, _ in host) == sorted(
            ["jaxmc.search", "jaxmc.search.seed"]
            + ["jaxmc.search.dispatch", "jaxmc.search.fetch"] * 2)
        # the JSONL's parent ids, found again as containment in the trace
        by_id = {e["id"]: e for e in _events(tmp_path / "t.jsonl")
                 if e["ev"] == "span"}
        order = {}
        for name, s, e in sorted(host, key=lambda t: t[1]):
            order.setdefault(name, []).append((s, e))
        seen = {}
        for sid in sorted(by_id):            # ids follow open order
            ev = by_id[sid]
            k = seen[ev["name"]] = seen.get(ev["name"], -1) + 1
            ev["xspan"] = order["jaxmc." + ev["name"]][k]
        for ev in by_id.values():
            if ev["parent_id"] is not None:
                ps, pe = by_id[ev["parent_id"]]["xspan"]
                s, e = ev["xspan"]
                assert ps <= s and e <= pe, (ev["name"], ev["parent_id"])


def _lowered(engine):
    import jax.numpy as jnp
    from jaxmc.backend.bfs import TpuExplorer
    sess = _session("constoy", "constoy", engine, obs.NullTelemetry())
    sess.compile()
    ex = sess.engine
    assert isinstance(ex, TpuExplorer)
    i32 = jnp.int32
    if engine == "resident":
        SC, FCap, AccCap, CH = 1 << 10, 64, 1 << 9, 64
        fn = ex._get_resident_run(SC, FCap, AccCap, min(128, ex.A * CH), CH)
        args = (jnp.zeros((SC, ex.K), i32), i32(0),
                jnp.zeros((FCap, ex.PW), i32)) + (i32(0),) * 7
    else:
        fn = ex._get_step(1 << 10, 64)
        args = (jnp.zeros((1 << 10, ex.K), i32), i32(0),
                jnp.zeros((64, ex.PW), i32), i32(0))
    return fn.__wrapped__.lower(*args)


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_lowered_program_names_all_seven_kernels(engine):
    """The scopes are metadata: there with debug info, and the program
    text without it does not know them."""
    pytest.importorskip("jax")
    low = _lowered(engine)
    text = low.as_text(debug_info=True)
    for scope in SCOPES:
        assert f"/{scope}/" in text, scope
    assert "jaxmc." not in low.as_text()


def _checked(spec, cfg, engine, tel, profile_dir=None, **opts):
    # the engine's own programs, not an earlier engine's of the same
    # test (the process's registry, ISSUE 37): compiles are compared
    forget_programs()
    with obs.use(tel):
        sess = _session(spec, cfg, engine, tel, **opts)
        if profile_dir is None:
            res = sess.explore()
        else:
            with _Profiled(profile_dir):
                res = sess.explore()
    return res


def _answer(res):
    return (res.ok, res.generated, res.distinct, res.diameter,
            None if res.violation is None else format_trace(res.violation))


@pytest.mark.parametrize("engine", sorted(ENGINES))
class TestNothingMovesWithTheSpansIn:
    def test_portoy_bad_answer_dispatches_and_compiles(self, engine,
                                                       tmp_path):
        """Spans + annotations under a live profiler session against no
        telemetry at all: the same verdict and trace; against a live
        recorder outside a session: the same dispatches and compiles."""
        pytest.importorskip("jax")
        plain = _checked("portoy", "portoy_bad", engine,
                         obs.NullTelemetry())
        tel_a, tel_b = obs.Telemetry(), obs.Telemetry()
        quiet = _checked("portoy", "portoy_bad", engine, tel_a)
        traced = _checked("portoy", "portoy_bad", engine, tel_b,
                          tmp_path / "xla")
        assert plain.violation.kind == "invariant"
        assert _answer(traced) == _answer(quiet) == _answer(plain)
        for tel in (tel_a, tel_b):
            assert tel.counters["compile.xla_compiles"] > 0
        assert tel_a.counters["compile.xla_compiles"] == \
            tel_b.counters["compile.xla_compiles"]
        disp = [{n: s.dispatches for n, s in t.prof.sites.items()}
                for t in (tel_a, tel_b)]
        assert disp[0] == disp[1]
        site = "bfs.resident_run" if engine == "resident" \
            else "bfs.level_step"
        assert disp[0][site] >= 1
        names = {n for n, _, _ in _host_spans(str(tmp_path / "xla"))}
        assert {"jaxmc.search", "jaxmc.search.init",
                "jaxmc.search.seed"} <= names
        assert names >= ({"jaxmc.search.dispatch", "jaxmc.search.fetch"}
                         if engine == "resident" else
                         {"jaxmc.level.dispatch", "jaxmc.level.sync"})

    def test_transfer_scaled_meets_its_pins(self, engine, tmp_path):
        pytest.importorskip("jax")
        tel = obs.Telemetry()
        res = _checked("transfer_scaled", "transfer_scaled", engine, tel,
                       tmp_path / "xla")
        assert (res.ok, res.generated, res.distinct, res.diameter) == \
            (True, 311153, 153701, 9)            # jaxmc/corpus.py
        # every generated state but the 12^3 initial ones (three
        # processes, money in 1..12) was a valid row of some level's sort
        assert tel.counters["search.rows_valid"] == 311153 - 12 ** 3
        # ... and every distinct one but those a new row of the seen table
        assert tel.counters["search.rows_new"] == 153701 - 12 ** 3
        if engine == "level":
            # the probe searched the query blocks that held a valid row
            # and no other: QB = max(2^12, A x FC / 64) with A = 10 and
            # FC 2^11, 2^13, 2^14, 2^15, then 2^16 for six levels, over
            # the levels' 5184, 15552, 31104, 57888, 70422, 62889, 39810,
            # 19664, 5184, 1728 generated states (bench/pins): (2 + 4 +
            # 8) x 4096 + 12 x 5120 + (7 + 7 + 4 + 2 + 1 + 1) x 10240 of
            # the 4,526,080 slots it used to search
            assert tel.counters["search.slots_probed"] == 344064
            assert tel.counters["search.slots_sorted"] == 4526080
            # ... and the merge built the blocks of the table that held
            # a live row after each level (bench/pins' levels): the
            # table holds SC = 2^15, 2^17, 2^18, 2^19, then 2^20 slots
            # for six levels (seen + A x FC candidates must fit), a
            # block is B(SC) of them
            from jaxmc.backend.bfs import _merge_block_rows
            scs = [1 << 15, 1 << 17, 1 << 18, 1 << 19] + 6 * [1 << 20]
            assert tel.counters["search.seen_slots"] == sum(scs)
            assert tel.counters["search.slots_merged"] == sum(
                -(-seen2 // _merge_block_rows(sc)) * _merge_block_rows(sc)
                for seen2, sc in zip(_PINS_3P_SEEN2, scs))
            assert tel.prof.sites["bfs.level_step"].dispatches == 10
            assert tel.prof.sites["bfs.level_step"].recompiles == \
                tel.counters["compile.cache_misses"]
        phases = {p["name"]: p["count"] for p in tel.phase_list()}
        assert phases["search.init"] == phases["search.finish"] == 1


def _pins(name):
    with open(os.path.join(REPO, "bench", "pins", name + ".json")) as fh:
        return json.load(fh)


def _seen_after_each_level(pins):
    """Rows the seen table holds after each level: the initial states
    and every level's new ones (the pinned models have no CONSTRAINT, so
    every fingerprinted state is a distinct one)."""
    new = [lv[2] for lv in pins["levels"]]
    have = pins["distinct"] - sum(new)
    return [have := have + n for n in new]


_PINS_3P_SEEN2 = _seen_after_each_level(_pins("transfer_scaled"))


def test_slots_merged_of_the_pinned_model_at_the_cells_caps():
    """The resident engine at the capacities the benchmark pins for the
    3-process model (no regrowth): `search.slots_merged` of a whole
    search is the sum over the pins' levels of ceil(seen_count2 / B) x B,
    B = B(SC 2^20) — what `merge_fill` divides by in `desk-recheck-3p`."""
    pytest.importorskip("jax")
    from jaxmc.backend.bfs import _merge_block_rows
    pins = _pins("transfer_scaled")
    tel = obs.Telemetry()
    res = _checked("transfer_scaled", "transfer_scaled", "resident", tel,
                   res_caps=dict(pins["res_caps"]))
    assert (res.generated, res.distinct) == (pins["generated"],
                                             pins["distinct"])
    B = _merge_block_rows(pins["res_caps"]["SC"])
    c = tel.counters
    assert c["search.seen_slots"] == len(pins["levels"]) \
        * pins["res_caps"]["SC"]
    assert c["search.slots_merged"] == sum(
        -(-seen2 // B) * B for seen2 in _PINS_3P_SEEN2)
    assert c["search.slots_merged"] < c["search.seen_slots"]
    assert c["search.rows_new"] == pins["distinct"] - 12 ** 3


@pytest.mark.slow
def test_slots_merged_of_the_4_process_pinned_model():
    """The same for `desk-recheck-4p8`'s model and capacities (minutes
    on XLA:CPU: not in the tier-1 run)."""
    pytest.importorskip("jax")
    from jaxmc.backend.bfs import _merge_block_rows
    pins = _pins("transfer_scaled_4p8")
    tel = obs.Telemetry()
    res = _checked("transfer_scaled", "transfer_scaled_4p8", "resident", tel,
                   res_caps=dict(pins["res_caps"]))
    assert (res.generated, res.distinct) == (pins["generated"],
                                             pins["distinct"])
    B = _merge_block_rows(pins["res_caps"]["SC"])
    assert tel.counters["search.slots_merged"] == sum(
        -(-seen2 // B) * B for seen2 in _seen_after_each_level(pins))


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_capacity_counters_by_hand(engine):
    """constoy: two counters a, b; IncA / IncB (A = 2 actions); CONSTRAINT
    a + b <= 5.  Level k = 0..5 holds the k + 1 states with a + b = k and
    generates 2 (k + 1) successors; level 5's all break the constraint, so
    the search ends after 6 levels with 2 * 21 = 42 generated past the
    one initial state."""
    pytest.importorskip("jax")
    tel = obs.Telemetry()
    caps = {"SC": 1 << 12, "FCap": 1 << 11, "AccCap": 1 << 13, "VC": 128}
    res = _checked("constoy", "constoy", engine, tel,
                   **({"res_caps": caps} if engine == "resident" else {}))
    assert (res.generated, res.distinct) == (43, 21)
    c = tel.counters
    assert c["search.rows_valid"] == 42
    assert c["search.rows_new"] == 20
    if engine == "level":
        # FC = 256 (the floor), so the candidate block is A * FC = 512
        # slots a level; the seen table grows once, to hold 1 + 512
        assert c["search.slots_sorted"] == 6 * 2 * 256
        assert c["search.seen_slots"] == 6 * 1024
    else:
        # the caps handed in: each level rewrites SC seen rows, whatever
        # it holds, and sorts the smallest rung of AccCap's ladder that
        # holds its 2 to 12 candidates — the one rung there is under two
        # floors (test_slots_sorted_by_hand cuts it)
        from jaxmc.backend.bfs import _sort_rungs
        assert _sort_rungs(1 << 13) == (1 << 13,)
        assert c["search.slots_sorted"] == 6 * (1 << 13)
        assert c["search.seen_slots"] == 6 * (1 << 12)


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_seed_and_table_bytes_by_hand(engine):
    """constoy at the capacities above: `search.seed_bytes` is the bytes
    of the HEADS the host hands the device at the start of a search — the
    one init state's key and its packed row, int32; the capacity-sized
    tables are filled on the device (ISSUE 35; up to PR 34 it was both
    tables at full capacity, built on the host).  `search.table_bytes` is
    the engine's capacity-sized tables at the capacities in force at its
    end, defined per engine (ISSUE 30; `bench/SPANS.deep.md`).  A second
    search hands over as much again; the gauge stays."""
    pytest.importorskip("jax")
    tel = obs.Telemetry()
    caps = {"SC": 1 << 12, "FCap": 1 << 11, "AccCap": 1 << 13, "VC": 128}
    with obs.use(tel):
        sess = _session("constoy", "constoy", engine, tel,
                        **({"res_caps": caps} if engine == "resident"
                           else {}))
        assert sess.explore().distinct == 21
        K, PW = sess.engine.K, sess.engine.PW
        if engine == "level":
            # exact 1-word keys under a validity lane; the seeded tables
            # are the floors FC = SC = 256, the seen table then grows
            # once, to 1024, to hold 1 + A x FC candidates
            assert (K, PW) == (2, 1)
            table = 4 * (1024 * K + 256 * PW)
        else:
            # 128-bit fingerprints under a validity lane; the
            # accumulator carries keys and rows
            assert (K, PW) == (5, 1)
            table = 4 * (caps["SC"] * K + caps["FCap"] * PW
                         + caps["AccCap"] * (K + PW))
        n_init = 1
        seed = 4 * n_init * (K + PW)
        assert tel.counters["search.seed_bytes"] == seed
        assert tel.gauges["search.table_bytes"] == table
        assert sess.explore().distinct == 21
    assert tel.counters["search.seed_bytes"] == 2 * seed
    assert tel.gauges["search.table_bytes"] == table


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_slots_probed_by_hand(engine, monkeypatch):
    """constoy again: level k = 0..5 hands the merge 2 (k + 1) valid keys.
    `search.slots_probed` is the sum over the levels of ceil(valid / QB)
    x QB, QB = max(floor, N / 64) of the merge's N key slots — the blocks
    of sorted keys the binary searches visited."""
    pytest.importorskip("jax")
    from jaxmc.backend import bfs
    monkeypatch.setattr(bfs, "_PROBE_BLOCK_MIN", 4)
    tel = obs.Telemetry()
    # chunk 64 lets the caps be this small (FCap's floor is the chunk)
    caps = {"SC": 256, "FCap": 64, "AccCap": 128, "VC": 64}
    res = _checked("constoy", "constoy", engine, tel,
                   **({"res_caps": caps, "chunk": 64}
                      if engine == "resident" else {}))
    assert (res.generated, res.distinct) == (43, 21)
    c = tel.counters
    if engine == "level":
        # N = A x FC = 512, QB 8: 2, 4, 6, 8, 10, 12 keys are
        # 1 + 1 + 1 + 1 + 2 + 2 blocks
        assert bfs._probe_block_rows(512) == 8
        assert c["search.slots_probed"] == 8 * 8
        assert c["search.slots_sorted"] == 6 * 512
    else:
        # N = AccCap = 128 (its floor), QB 4: 2, 4, 6, 8, 10, 12 keys
        # are 1 + 1 + 2 + 2 + 3 + 3 blocks
        assert bfs._probe_block_rows(128) == 4
        assert c["search.slots_probed"] == 12 * 4
        assert c["search.slots_sorted"] == 6 * bfs._sort_rungs(128)[-1] \
            == 6 * 128


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_slots_sorted_by_hand(engine, monkeypatch):
    """constoy a third time: level k = 0..5 hands the merge 2 (k + 1)
    valid keys.  The level engine sorts its whole candidate block a
    level; the resident engine's candidates are a prefix of its
    accumulator, and `search.slots_sorted` is the sum over the levels of
    the smallest rung of AccCap, AccCap / 2, ... down to the floor that
    holds them (ISSUE 42)."""
    pytest.importorskip("jax")
    from jaxmc.backend import bfs
    monkeypatch.setattr(bfs, "_SORT_RUNG_MIN", 4)
    tel = obs.Telemetry()
    caps = {"SC": 256, "FCap": 64, "AccCap": 128, "VC": 64}
    res = _checked("constoy", "constoy", engine, tel,
                   **({"res_caps": caps, "chunk": 64}
                      if engine == "resident" else {}))
    assert (res.generated, res.distinct) == (43, 21)
    c = tel.counters
    if engine == "level":
        assert c["search.slots_sorted"] == 6 * 512
    else:
        # 2, 4, 6, 8, 10, 12 keys on the rungs 128, 64, 32, 16, 8, 4
        assert bfs._sort_rungs(128) == (128, 64, 32, 16, 8, 4)
        assert c["search.slots_sorted"] == 4 + 4 + 8 + 8 + 16 + 16
    assert c["search.rows_valid"] == 42


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_slots_merged_by_hand(engine, monkeypatch):
    """constoy once more: after level k = 0..5 the seen table holds the
    (k + 2)(k + 3) / 2 states with a + b <= k + 1 — level 5's successors
    break the CONSTRAINT and are fingerprinted all the same: 3, 6, 10,
    15, 21, 28 rows.  `search.slots_merged` is the sum over the levels
    of ceil(rows / B) x B, B rows the block of the merged table."""
    pytest.importorskip("jax")
    from jaxmc.backend import bfs
    monkeypatch.setattr(bfs, "_MERGE_BLOCK_ROWS", 8)
    tel = obs.Telemetry()
    caps = {"SC": 256, "FCap": 64, "AccCap": 128, "VC": 64}
    res = _checked("constoy", "constoy", engine, tel,
                   **({"res_caps": caps, "chunk": 64}
                      if engine == "resident" else {}))
    assert (res.generated, res.distinct) == (43, 21)
    c = tel.counters
    assert bfs._merge_block_rows(256) == bfs._merge_block_rows(1024) == 8
    # 1 + 1 + 2 + 2 + 3 + 4 blocks of the 32 (resident, SC 256) or 128
    # (level engine, SC 1024) a level that the table is cut into
    assert c["search.slots_merged"] == 13 * 8
    assert c["search.seen_slots"] == 6 * (256 if engine == "resident"
                                          else 1024)


def test_compile_seconds_by_program():
    """`compile.by_fun` names the engines' programs as jax names them and
    splits `compile.xla_compile_s` without a remainder."""
    pytest.importorskip("jax")
    tel = obs.Telemetry()
    for engine in sorted(ENGINES):
        _checked("constoy", "constoy", engine, tel)
    by_fun = tel.gauges["compile.by_fun"]
    assert by_fun["run"][0] >= 1 and by_fun["step"][0] >= 1
    assert by_fun["run"][1] > 0 and by_fun["step"][1] > 0
    assert sum(n for n, _ in by_fun.values()) == \
        tel.counters["compile.xla_compiles"]
    assert sum(s for _, s in by_fun.values()) == pytest.approx(
        tel.counters["compile.xla_compile_s"])
    json.dumps(tel.summary())     # the table is plain JSON in the artifact
