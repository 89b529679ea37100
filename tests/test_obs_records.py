r"""Records by identity (ISSUE 34): what each compiled program holds and
what the host did in each search.

`phases`, `prof.sites` and `compile.xla_compile_s` are sums by NAME.  Beside
them the recorder now keeps one record per EXECUTABLE (`prof.programs`:
site, the engine's cache key, compiled or loaded, `memory_analysis()` of the
executable the dispatch itself made — never a second compile) and one per
SEARCH (`requests`: the walls of its spans, the host-seconds counters
`seed.keys_s` / `.tables_s` / `.upload_s` / `dispatch.launch_s`, its CPU
seconds, the origin of what it dispatched).  All of it is host code: no
count, verdict or lowered program may move, and a NullTelemetry pays nothing.
"""

import io
import json
import os
import subprocess
import sys
import time

import pytest

from jaxmc import obs
from jaxmc.compile.cache import forget_programs
from jaxmc.obs import prof as prof_mod
from jaxmc.obs import telemetry
from jaxmc.session import CheckSession, SessionConfig

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = os.path.join(REPO, "specs")
ENGINES = {"level": {}, "resident": {"resident": True, "no_trace": True},
           "mesh": {"devices": 4}}
# the search program's dispatch site, and the name jax compiles it under
SITE = {"level": ("bfs.level_step", "step"),
        "resident": ("bfs.resident_run", "run"),
        "mesh": ("mesh.superstep", "device_step")}
BYTES = ("argument_bytes", "output_bytes", "alias_bytes", "temp_bytes")
SEED = ("seed.keys_s", "seed.tables_s", "seed.upload_s")


@pytest.fixture(autouse=True)
def _no_capacity_profiles(monkeypatch):
    # capacities from the engines' own defaults, whatever ran before
    monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")


def _session(engine, tel, spec="constoy", cfg="constoy"):
    return CheckSession(SessionConfig(
        spec=os.path.join(SPECS, spec + ".tla"),
        cfg=os.path.join(SPECS, cfg + ".cfg"), backend="jax",
        platform="cpu", **ENGINES[engine]), tel=tel)


def _searched(engine, searches=1, **tel_kw):
    """(tel, results, session) of `searches` whole searches of constoy on
    one session under a live recorder.  The session's engine makes its
    own programs, whatever an earlier engine of this test left in the
    process's registry (ISSUE 37): what is counted here is a compile."""
    forget_programs()
    tel = obs.Telemetry(**tel_kw)
    with obs.use(tel):
        sess = _session(engine, tel)
        results = [sess.explore() for _ in range(searches)]
    for r in results:
        assert (r.ok, r.generated, r.distinct) == (True, 43, 21)
    return tel, results, sess


# ------------------------------------------------- one record per program

@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_each_executable_leaves_one_record_with_what_it_holds(engine):
    """A toy search on each engine: one record per executable, in
    first-dispatch order, with the site, the engine's own cache key, the
    origin and `memory_analysis()`'s four fields; the largest is the
    gauges'.  With donation off XLA:CPU reports no aliased bytes: that
    one is held to >= 0, the other three to > 0.  No skip: a jax that
    stops keeping the executable where `_executable_bytes` reads it
    turns this red, not the byte metrics silently None."""
    tel, _, sess = _searched(engine, searches=2)
    programs = tel.prof.programs
    site, fun = SITE[engine]
    assert [p["site"] for p in programs] == ["bfs.host_keys", site]
    # one record per executable: as many as the sites' cache sizes grew
    assert len(programs) == sum(s.recompiles
                                for s in tel.prof.sites.values())
    for p in programs:
        assert p["origin"] == "compiled"       # the suite runs cache-off
        assert p["xla_s"] > 0 and p["dispatches"] >= 2
        assert set(BYTES) < set(p), \
            f"{p['site']}: jax keeps no executable where prof.py reads it"
        for f in ("argument_bytes", "output_bytes", "temp_bytes"):
            assert isinstance(p[f], int) and p[f] > 0, (p["site"], f)
        assert isinstance(p["alias_bytes"], int) and p["alias_bytes"] >= 0
        assert p["hbm_bytes"] == p["argument_bytes"] + p["output_bytes"] \
            - p["alias_bytes"] + p["temp_bytes"]
    keys, search = programs
    assert keys["key"] == 8                    # _host_keys' bucket
    cache = {"level": "_step_cache", "resident": "_res_cache",
             "mesh": "_mesh_step_cache"}[engine]
    # the engine's OWN cache key, as JSON holds a tuple
    assert tuple(search["key"]) in getattr(sess.engine, cache)
    top = max(programs, key=lambda p: p["hbm_bytes"])
    assert top is search
    # the two gauges with a reader (bench/layers/program_*_mb.py)
    assert {g: v for g, v in tel.gauges.items()
            if g.startswith("program.")} == {
        "program.temp_bytes": top["temp_bytes"],
        "program.hbm_bytes": top["hbm_bytes"]}
    # one XLA compile a program, under the name jax gives it
    by_fun = tel.gauges["compile.by_fun"]
    assert by_fun[fun][0] == 1 and by_fun["<lambda>"][0] == 1
    assert search["xla_s"] == pytest.approx(by_fun[fun][1], abs=1e-5)
    # the records are plain JSON in the artifact's prof{} block
    block = json.loads(json.dumps(tel.summary()))["prof"]
    assert block["programs"] == json.loads(json.dumps(programs))
    assert block["sites"][site]["launch_s"] > 0


@pytest.mark.parametrize("engine", ["resident", "mesh"])
def test_reading_the_executable_is_no_second_compile(engine, monkeypatch):
    """The rise of `compile.xla_compiles` over a run with the executables
    read equals the rise over the same run with the reading switched off:
    one XLA compile (or load) a program, never two.  And the program
    records are as many as the compiles jax names after the programs."""
    pytest.importorskip("jax")
    _searched(engine)   # jax keeps the eager helpers' programs per process
    tel_read, _, _ = _searched(engine)
    monkeypatch.setattr(prof_mod, "_executable_bytes",
                        lambda fn, args, kwargs: {})
    tel_blind, _, _ = _searched(engine)
    assert tel_read.counters["compile.xla_compiles"] == \
        tel_blind.counters["compile.xla_compiles"]
    assert "temp_bytes" in tel_read.prof.programs[-1]
    assert "temp_bytes" not in tel_blind.prof.programs[-1]
    assert "program.temp_bytes" not in tel_blind.gauges
    by_fun = tel_read.gauges["compile.by_fun"]
    named = by_fun[SITE[engine][1]][0] + by_fun["<lambda>"][0]
    assert named == len(tel_read.prof.programs) == 2


def test_a_later_engine_records_the_program_its_process_holds(tmp_path):
    """The third origin (ISSUE 37): a second engine of the same model asks
    jax for nothing — its recorder still holds one record per program, the
    maker's bytes under origin "held", its own dispatch counts, the two
    gauges, `compile.xla_compile_s` at 0.0; the search record says what
    it dispatched was held; `obs top` and `obs report` print it."""
    pytest.importorskip("jax")
    from jaxmc.obs.report import main
    made, _, _ = _searched("resident")
    tel = obs.Telemetry()
    with obs.use(tel):
        sess = _session("resident", tel)
        res = [sess.explore() for _ in range(2)]
    assert all((r.ok, r.generated, r.distinct) == (True, 43, 21)
               for r in res)
    assert [p["site"] for p in tel.prof.programs] == \
        ["bfs.host_keys", "bfs.resident_run"]
    for p, was in zip(tel.prof.programs, made.prof.programs):
        assert (was["origin"], p["origin"]) == ("compiled", "held")
        assert p["xla_s"] == 0.0 and p["dispatches"] >= 2
        assert {f: p[f] for f in BYTES + ("hbm_bytes", "key", "site")} == \
            {f: was[f] for f in BYTES + ("hbm_bytes", "key", "site")}
    top = tel.prof.programs[-1]
    assert tel.gauges["program.temp_bytes"] == top["temp_bytes"]
    assert tel.gauges["program.hbm_bytes"] == top["hbm_bytes"]
    assert tel.counters["compile.program_hits"] == 2
    assert tel.counters["compile.xla_compile_s"] == 0.0
    assert tel.counters.get("compile.xla_compiles", 0) == 0
    assert all(s.recompiles == 0 for s in tel.prof.sites.values())
    assert [set(r["origins"]) for r in tel.requests] == [{"held"}] * 2
    assert sum(r["dispatches"] for r in tel.requests) == \
        sum(p["dispatches"] for p in tel.prof.programs)
    path = str(tmp_path / "held.json")
    tel.write_metrics(path, result={
        "ok": True, "distinct": 21, "generated": 43,
        "diameter": res[0].diameter, "truncated": False})
    out = io.StringIO()
    assert main(["top", path], out=out) == 0
    assert [ln.split()[:2] for ln in out.getvalue().splitlines()
            if ln.split()[1:2] == ["held"]] == [
        ["bfs.host_keys", "held"], ["bfs.resident_run", "held"]]
    out = io.StringIO()
    assert main(["report", path], out=out) == 0
    text = out.getvalue()
    assert "programs: 2 held by the process (no trace, no load), 0 made " \
        "new, 0 unkeyed" in text
    assert "dispatches by origin {'held': " in text


def test_a_backend_that_keeps_no_executable_gives_a_record_without_bytes():
    """Where jax holds no executable to read (a callable that is no jit,
    or a lowering whose executable is gone) the record has its identity
    and no byte field — and nothing is compiled to fill them."""

    class Grows:
        n = 0

        def _cache_size(self):
            return self.n

        def __call__(self, x):
            self.n += 1
            return x

    p = obs.Profiler()
    p.record("t.site", Grows(), (1,), {}, key=(4, 2))
    (rec,) = p.programs
    assert rec == {"site": "t.site", "key": [4, 2], "origin": "compiled",
                   "xla_s": 0.0, "dispatches": 1}

    class Lowered:
        _lowering = type("L", (), {"_executable": None})()

        def compile(self):
            raise AssertionError("a second compile")

    fn = Grows()
    fn.lower = lambda *a, **k: Lowered()
    p.record("t.other", fn, (1,), {})
    assert set(p.programs[-1]) == set(rec)


def test_dispatches_go_to_the_function_called_not_to_a_site_and_key():
    """Two jitted functions under ONE site and no key (the engines' sites
    without a cache key) keep a record each and their own dispatches; a
    function that makes a second executable charges its later dispatches
    to the newest, and the site's `recompiles` says it happened."""
    import jax
    import jax.numpy as jnp
    tel = obs.Telemetry()
    f = obs.prof_wrap("t.site", jax.jit(lambda x: x + 1))
    g = obs.prof_wrap("t.site", jax.jit(lambda x: x * 2))
    with obs.use(tel):
        for _ in range(3):
            f(jnp.zeros(4))
        g(jnp.zeros(4))
        f(jnp.zeros(4))
        assert [r["dispatches"] for r in tel.prof.programs] == [4, 1]
        f(jnp.zeros(8))            # a second executable of f
        f(jnp.zeros(4))            # the first again: charged to the newest
    assert [r["dispatches"] for r in tel.prof.programs] == [4, 1, 2]
    assert tel.prof.sites["t.site"].recompiles == 3
    assert tel.prof.sites["t.site"].dispatches == 7


_TWO_PROCESSES = r"""
import json, os, sys
sys.path.insert(0, {repo!r})
import jax
jax.config.update('jax_platforms', 'cpu')
import jax.numpy as jnp
from jaxmc import obs
from jaxmc.compile import cache
tel = obs.Telemetry()
assert cache.enable_guarded_cache(tel=tel) == {d!r}
fn = obs.prof_wrap('t.site', jax.jit(
    lambda x, y: jnp.sort(x * 3 + y)[::-1].cumsum()), key=(64,))
with obs.use(tel):
    for _ in range(3):
        fn(jnp.arange(64), jnp.ones(64, jnp.int32)).block_until_ready()
print('RECORDS', json.dumps([tel.prof.programs,
                             tel.counters['compile.xla_compiles']]))
"""


def test_the_second_process_on_one_cache_directory_loaded(tmp_path):
    """Two processes on one cache directory (as tests/test_cache_guard.py
    makes XLA:CPU's cache hit): the first's record says `compiled`, the
    second's `loaded`, each with ONE record for three dispatches, the
    same bytes — a loaded executable answers `memory_analysis()` too —
    and the wrapped program's compile counted once."""
    pytest.importorskip("jax")
    d = str(tmp_path / "placed")
    code = _TWO_PROCESSES.format(repo=REPO, d=d)
    got = []
    for _ in range(2):
        p = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=240,
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     JAX_COMPILATION_CACHE_DIR=d,
                     JAXMC_COMPILE_CACHE="on", JAXMC_CACHE_PROBE="0"))
        assert p.returncode == 0, p.stderr[-800:]
        got.append(json.loads(p.stdout.split("RECORDS")[1]))
    (first,), (second,) = got[0][0], got[1][0]
    assert (first["origin"], second["origin"]) == ("compiled", "loaded")
    assert first["dispatches"] == second["dispatches"] == 3
    assert first["key"] == second["key"] == [64]
    for f in BYTES + ("hbm_bytes",):
        assert first[f] == second[f], f
    assert first["temp_bytes"] > 0 and second["xla_s"] > 0
    # eager helpers (arange, ones) compile too; the reading adds none:
    # both processes ask XLA as often
    assert got[0][1] == got[1][1]


# ------------------------------------------- the host's pieces of a search

@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_the_seed_counters_add_up_to_the_seed_span_and_never_fall(engine):
    """`seed.keys_s` + `.tables_s` + `.upload_s` are `search.seed`'s wall:
    never more (they are taken inside it) and, at toy size where the
    span's own opening and closing weigh, within 10 % + 2 ms.  Over three
    searches none of them falls, and the search records' rises are the
    counters'."""
    pytest.importorskip("jax")
    tel = obs.Telemetry()
    seen = []
    with obs.use(tel):
        sess = _session(engine, tel)
        for _ in range(3):
            sess.explore()
            seen.append([tel.counters[c] for c in SEED]
                        + [tel.counters["dispatch.launch_s"]])
    for before, after in zip(seen, seen[1:]):
        assert all(b <= a for b, a in zip(before, after))
        assert sum(after[:3]) > sum(before[:3])
    wall = {p["name"]: p["wall_s"] for p in tel.phase_list()}["search.seed"]
    pieces = sum(seen[-1][:3])
    assert pieces <= wall + 1e-6
    assert pieces >= 0.9 * wall - 0.002 * 3, (pieces, wall)
    for i, name in enumerate(SEED + ("dispatch.launch_s",)):
        assert sum(r["counters"][name] for r in tel.requests) == \
            pytest.approx(seen[-1][i], abs=1e-5)
    # the launch seconds by site add up to the counter
    assert sum(s.launch_s for s in tel.prof.sites.values()) == \
        pytest.approx(tel.counters["dispatch.launch_s"])
    # a warm search's seed is host work, not a compile: the first paid
    # `_host_keys`' program, the others do not
    first, *warm = tel.requests
    assert first["counters"]["seed.keys_s"] > \
        5 * max(r["counters"]["seed.keys_s"] for r in warm)


# ------------------------------------------------------ one record a search

def test_n_searches_leave_n_records_and_the_stream_holds_no_copy(tmp_path):
    """One sink: the records live in the recorder's deque and the
    summary.  The trace stream gets neither a `request` event nor a
    `rid` on its span events (nothing reads a stream by search), so a
    span under a search pays no extra key."""
    pytest.importorskip("jax")
    trace = str(tmp_path / "t.jsonl")
    tel, _, _ = _searched("resident", searches=3, trace_path=trace)
    tel.close()
    with open(trace) as fh:
        events = [json.loads(ln) for ln in fh if ln.strip()]
    for ev in events:
        obs.validate_trace_event(ev)
    assert not [e for e in events if e["ev"] == "request"]
    spans = [e for e in events if e["ev"] in ("span", "span_open")]
    assert spans and not [e for e in spans if "rid" in e]
    searches = [e for e in spans
                if e["ev"] == "span" and e["name"] == "search"]
    assert [r["rid"] for r in tel.requests] == [1, 2, 3]
    for rec, ev in zip(tel.requests, sorted(searches,
                                            key=lambda e: e["t0"])):
        assert set(rec) == {"rid", "name", "t0", "wall_s", "cpu_s", "spans",
                            "counters", "dispatches", "origins"}
        assert rec["name"] == "search" and rec["wall_s"] == ev["wall_s"]
        assert set(rec["spans"]) == {"search.init", "search.seed",
                                     "search.dispatch", "search.fetch",
                                     "search.finish"}
        assert sum(rec["spans"].values()) <= rec["wall_s"] + 1e-5
        assert set(rec["counters"]) == set(telemetry.REQUEST_COUNTERS)
        assert rec["cpu_s"] > 0
        assert rec["origins"] == {"compiled": rec["dispatches"]}
    # dispatches of the records = dispatches of the sites
    assert sum(r["dispatches"] for r in tel.requests) == \
        sum(s.dispatches for s in tel.prof.sites.values())
    assert json.loads(json.dumps(tel.summary()))["requests"] == \
        json.loads(json.dumps(list(tel.requests)))


def test_the_records_are_bounded_and_need_no_jax(monkeypatch):
    """A window of any length keeps the last N records; a span that is no
    request leaves none; requests of two threads do not mix (the stack is
    per thread)."""
    monkeypatch.setattr(telemetry, "_REQUESTS_MAX", 4)
    tel = obs.Telemetry()
    with tel.span("load"):
        pass
    for i in range(10):
        with tel.request("search", i=i):
            with tel.span("search.seed"):
                with tel.timed("seed.tables_s"):
                    pass
            tel.counter("dispatch.launch_s", 0.5)
    assert [r["rid"] for r in tel.requests] == [7, 8, 9, 10]
    assert tel.requests.maxlen == 4
    for r in tel.requests:
        assert set(r["spans"]) == {"search.seed"}
        assert r["counters"]["dispatch.launch_s"] == 0.5
        assert r["counters"]["seed.keys_s"] == 0
        assert r["dispatches"] == 0 and r["origins"] == {}
    assert tel.counters["seed.tables_s"] >= 0
    assert len(tel.summary()["requests"]) == 4
    # outside any jax: the module imported none
    code = ("import sys; from jaxmc import obs; t = obs.Telemetry()\n"
            "with t.request('search'):\n"
            "    with t.timed('seed.keys_s'): pass\n"
            "assert len(t.requests) == 1 and 'jax' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=REPO,
                   timeout=60)


def test_null_telemetry_pays_nothing(monkeypatch):
    """No recorder: no clock is read, no record is made — the wrapper is
    one getattr and a None test, `timed` and `request` the shared no-op."""
    null = obs.NullTelemetry()
    assert obs.current().enabled is False
    assert null.timed("seed.keys_s") is null.request("search") \
        is null.span("x")
    calls = []

    def fn(x):
        calls.append(x)
        return x

    fn._cache_size = lambda: len(calls)
    fn.lower = lambda *a: (_ for _ in ()).throw(AssertionError("lowered"))
    wrapped = obs.prof_wrap("t.site", fn, key=(1,))

    def no_clock():
        raise AssertionError("a clock was read with no recorder")

    monkeypatch.setattr(time, "perf_counter", no_clock)
    monkeypatch.setattr(time, "process_time", no_clock)
    with null.request("search"), null.timed("seed.tables_s"):
        assert wrapped(3) == 3
    assert calls == [3]
    assert not hasattr(null, "requests") and null.prof is None


# ----------------------------------------------- nothing of the search moves

def _lowered_resident_text():
    import jax.numpy as jnp
    sess = _session("resident", obs.current())
    sess.compile()
    ex, i32 = sess.engine, jnp.int32
    SC, FCap, AccCap, CH = 1 << 10, 64, 1 << 9, 64
    fn = ex._get_resident_run(SC, FCap, AccCap, min(128, ex.A * CH), CH)
    args = (jnp.zeros((SC, ex.K), i32), i32(0),
            jnp.zeros((FCap, ex.PW), i32)) + (i32(0),) * 7
    return fn.__wrapped__.lower(*args).as_text()


def test_counts_verdicts_and_lowered_text_with_and_without_a_recorder():
    pytest.importorskip("jax")
    from jaxmc.engine.explore import format_trace

    def answer(tel):
        forget_programs()   # each engine's own program, under its recorder
        with obs.use(tel):
            sess = _session("resident", tel, "portoy", "portoy_bad")
            res = sess.explore()
            return (res.ok, res.generated, res.distinct, res.diameter,
                    res.violation.kind, format_trace(res.violation),
                    _lowered_resident_text())

    plain = answer(obs.NullTelemetry())
    live = obs.Telemetry()
    assert answer(live) == plain
    assert plain[4] == "invariant"
    assert len(live.requests) == 1 and live.prof.programs


# ------------------------------------------------------------ the two CLIs

def test_top_prints_the_programs_and_report_the_searches(tmp_path):
    pytest.importorskip("jax")
    from jaxmc.obs.report import main
    tel, (res, *_), _ = _searched("resident", searches=4)
    path = str(tmp_path / "m.json")
    tel.write_metrics(path, result={
        "ok": res.ok, "distinct": res.distinct, "generated": res.generated,
        "diameter": res.diameter, "truncated": False})
    out = io.StringIO()
    assert main(["top", path], out=out) == 0
    text = out.getvalue()
    assert "programs (one per executable; bytes per device):" in text
    # the sites' own launch seconds have a reader: the table's last column
    head, site = (next(ln for ln in text.splitlines() if start in ln)
                  for start in ("  site ", "  bfs.resident_run  "))
    assert head.split()[-1] == "launch"
    assert float(site.split()[-1].rstrip("s")) == pytest.approx(
        tel.prof.sites["bfs.resident_run"].launch_s, abs=1e-4)
    row = next(ln for ln in text.splitlines()
               if ln.strip().startswith("bfs.resident_run  compiled"))
    assert str(tel.prof.programs[-1]["key"]) in row
    out = io.StringIO()
    assert main(["report", path], out=out) == 0
    text = out.getvalue()
    assert "searches: 4 records; wall median " in text
    # the first search compiled: it is the slowest, and the piece that
    # grew is the launch that held the compile
    assert "(rid 1)" in text
    assert "dispatch.launch_s grew most" in text or \
        "search.dispatch grew most" in text
