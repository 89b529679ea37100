r"""Device profiler (ISSUE 17): per-dispatch attribution.

PR 11 left one perf target unmet — merge wall <30% of step wall — partly
because nothing below the PHASE level said where device time went: a
phase wall names "the fused step is slow", not which dispatch site,
buffer traffic, or recompile paid for it.  This module is the missing
layer:

  sites     every jitted entry point in the engines registers a NAMED
            dispatch site via `wrap("bfs.level_step", jitted)`; the
            wrapper resolves the active recorder's Profiler at CALL
            time (so the serve daemon's per-thread recorders work
            unchanged) and records per-site stats.
  cheap     the always-on mode: dispatch counts + recompile attribution
            only (a `_cache_size()` delta around the call) — no sync,
            no byte walks, so profile-off runs stay byte-identical and
            effectively free.
  wall      `--profile`: additionally blocks until the output pytree is
            ready and charges the wall to the site, and sums argument /
            result bytes per dispatch.  Synchronization cannot change
            counts or traces — profile-on vs profile-off stays
            bit-identical (pinned by tests/test_prof.py).
  xla       cheap + the CLI wraps the run in a jax.profiler capture
            (no Python tracer) to a named artifact dir: the run a user
            has, with the program's spans (`jaxmc.<span>`, obs/
            telemetry.py) and kernel scopes (backend/bfs.py) in it;
            per-operation bytes and flops are the trace's own.
  hbm       the MEASURED device peak, `memory_stats()
            ["peak_bytes_in_use"]` summed over the live devices; absent
            where the backend reports none (XLA:CPU).
  programs  one record per executable (ISSUE 34): where a dispatch
            grows a site's `_cache_size()` the profiler asks jax for
            the executable THAT CALL made (`fn.lower(*args).compile()`
            after the call returns the cached `MeshComputation`'s
            executable: no second compile, no second cache load) and
            keeps its identity: the site, the engine's cache key,
            whether it was compiled or loaded from the persistent
            cache, the XLA seconds, and `memory_analysis()` per device
            — the temporaries `memory_stats()` never shows.  The
            program with the largest `hbm_bytes` so far is published as
            gauges `program.temp_bytes` / `program.hbm_bytes`.
            `origin` and `xla_s` are the rise of PROCESS-WIDE counters
            around the call: right where one thread compiles at a time
            (the CLI, the bench, the serve daemon's single device
            owner); a compile on another thread, or an eager helper
            first compiled inside the same call, is charged to it too.
            `dispatches` goes to the NEWEST executable of the jitted
            function called: a function that holds several (a
            recompile under one engine key; the site's `recompiles`
            says so) charges them all to the last.
  held      a third origin (ISSUE 37): a new engine that dispatches a
            program its PROCESS already holds (compile/cache.py's
            registry: same program signature, an earlier engine's
            jitted callable) makes no executable, so no site grows and
            `record` has nothing to read.  The registry calls `hold`
            instead: the record the executable got when it came into
            being (kept on the site's wrapper, `wrapper.program`) is
            appended to THIS recorder's `programs` with origin "held",
            `xla_s` 0.0 and its own `dispatches`, and the gauges are
            published as for a new executable.
  launch    every mode also charges the host seconds inside
            `fn(*args)` up to its RETURN (the enqueue; on a new
            executable the compile or load too) to the site and to the
            float counter `dispatch.launch_s`: the host's part of a
            dispatch on the program's own clock, tracer or not.

The rollup lands in the metrics artifact as the `prof{}` block (schema
jaxmc.metrics/4, obs/schema.py) and renders via `python -m jaxmc.obs
top` — the table that answers where the 44–77% goes.  This module is
import-clean of jax (the report CLI must run in interp-only
environments); jax is imported lazily inside the wall-mode paths only.
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

# resolved lazily to avoid a telemetry<->prof import cycle (telemetry
# imports Profiler at module load; we only need current() at call time)
_current = None


def _cur():
    global _current
    if _current is None:
        from .telemetry import current as _current
    return _current()


def _nbytes(x) -> int:
    """Best-effort byte count of a pytree-ish value without importing
    jax: arrays expose .nbytes; containers recurse; scalars are 0."""
    nb = getattr(x, "nbytes", None)
    if isinstance(nb, int):
        return nb
    if isinstance(x, dict):
        return sum(_nbytes(v) for v in x.values())
    if isinstance(x, (list, tuple)):
        return sum(_nbytes(v) for v in x)
    return 0


def _compile_marks(tel) -> Tuple[float, int]:
    """(XLA seconds, persistent-cache hits) as compile/cache.py's
    listeners have counted them into `tel` so far."""
    c = getattr(tel, "counters", None) or {}
    return (c.get("compile.xla_compile_s", 0.0),
            c.get("compile.persistent_cache_hits", 0))


_BYTE_FIELDS = (("argument_bytes", "argument_size_in_bytes"),
                ("output_bytes", "output_size_in_bytes"),
                ("alias_bytes", "alias_size_in_bytes"),
                ("temp_bytes", "temp_size_in_bytes"))


def _executable_bytes(fn, args, kwargs) -> Dict[str, int]:
    """`memory_analysis()` of the executable `fn(*args)` just ran, per
    device, read where jax keeps it: lowering the same arguments again
    returns the computation the call cached, executable and all (donated
    arguments are deleted by now; their avals are not).  {} where jax
    holds no executable there — this never compiles one."""
    try:
        lowered = fn.lower(*args, **kwargs)
        if getattr(getattr(lowered, "_lowering", None),
                   "_executable", None) is None:
            return {}
        ma = lowered.compile().memory_analysis()
        out = {ours: int(getattr(ma, theirs))
               for ours, theirs in _BYTE_FIELDS}
    except Exception:  # noqa: BLE001 — profiling never breaks a run
        return {}
    out["hbm_bytes"] = out["argument_bytes"] + out["output_bytes"] \
        - out["alias_bytes"] + out["temp_bytes"]
    return out


class SiteStats:
    """Per-site accumulators.  Mutated under the owning Profiler's
    lock; read via Profiler.snapshot()."""

    __slots__ = ("name", "dispatches", "wall_s", "arg_bytes",
                 "res_bytes", "recompiles", "launch_s")

    def __init__(self, name: str):
        self.name = name
        self.dispatches = 0
        self.wall_s = 0.0
        self.launch_s = 0.0
        self.arg_bytes = 0
        self.res_bytes = 0
        self.recompiles = 0

    def as_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {"dispatches": self.dispatches,
                             "recompiles": self.recompiles}
        if self.launch_s:
            d["launch_s"] = round(self.launch_s, 6)
        if self.wall_s:
            d["wall_s"] = round(self.wall_s, 6)
        if self.arg_bytes or self.res_bytes:
            d["arg_bytes"] = self.arg_bytes
            d["res_bytes"] = self.res_bytes
        return d


class Profiler:
    """One per live Telemetry (NullTelemetry carries `prof = None`, so
    the un-instrumented hot path costs one getattr + a None test)."""

    CHEAP, WALL, XLA = "cheap", "wall", "xla"

    def __init__(self, mode: str = "cheap",
                 clock=time.perf_counter):
        self.mode = mode
        self._clock = clock
        self._lock = threading.Lock()
        self.sites: Dict[str, SiteStats] = {}
        # one record per executable, in first-dispatch order, and the
        # newest of each jitted function (weak: an engine that drops
        # its program drops the entry; the record stays in the list)
        self.programs: List[Dict[str, Any]] = []
        self._program_of: "weakref.WeakKeyDictionary[Any, Dict[str, Any]]" \
            = weakref.WeakKeyDictionary()
        self.xla_trace_dir: Optional[str] = None

    # ---- dispatch sites ------------------------------------------------
    def _site(self, name: str) -> SiteStats:
        st = self.sites.get(name)
        if st is None:
            with self._lock:
                st = self.sites.setdefault(name, SiteStats(name))
        return st

    @staticmethod
    def _cache_size(fn) -> Optional[int]:
        cs = getattr(fn, "_cache_size", None)
        if not callable(cs):
            return None
        try:
            return int(cs())
        except Exception:  # noqa: BLE001 — profiling never breaks a run
            return None

    def record(self, name: str, fn, args, kwargs, key=None,
               wrapper=None):
        """One profiled dispatch.  Every mode: count, recompile delta,
        the host seconds up to `fn`'s return (`dispatch.launch_s`) and,
        where the call made a new executable, its program record (left
        on `wrapper`, the site's `wrap()`, for a later engine's
        recorder: `hold`).  Wall mode: + block-until-ready wall and
        arg/result bytes."""
        st = self._site(name)
        tel = _cur()  # the recorder jax's compile listeners write to
        marks = _compile_marks(tel)
        cs0 = self._cache_size(fn)
        t0 = self._clock()
        out = fn(*args, **kwargs)
        launch = self._clock() - t0
        dt = ab = rb = 0
        if self.mode == self.WALL:
            out = self._block(out)
            dt = self._clock() - t0
            ab = _nbytes(args) + _nbytes(kwargs)
            rb = _nbytes(out)
        cs1 = self._cache_size(fn)
        grew = cs1 - cs0 if cs0 is not None and cs1 is not None \
            and cs1 > cs0 else 0
        if grew:
            rec = self._new_program(name, key, fn, args, kwargs, tel,
                                    marks)
            if wrapper is not None:
                wrapper.program = rec
        with self._lock:
            st.dispatches += 1
            st.launch_s += launch
            st.wall_s += dt
            st.arg_bytes += ab
            st.res_bytes += rb
            st.recompiles += grew
            prog = self._program_of.get(fn)
            if prog is not None:
                prog["dispatches"] += 1
        tel.counter("dispatch.launch_s", launch)
        return out

    # ---- program records -------------------------------------------------
    def _new_program(self, name, key, fn, args, kwargs, tel, marks):
        """The record of the executable the call just made (module
        docstring, `programs`).  Never raises and never compiles."""
        xla_s, hits = (b - a for a, b in zip(marks, _compile_marks(tel)))
        rec: Dict[str, Any] = {
            "site": name,
            "key": list(key) if isinstance(key, tuple) else key,
            "origin": "loaded" if hits > 0 else "compiled",
            "xla_s": round(xla_s, 6), "dispatches": 0}
        rec.update(_executable_bytes(fn, args, kwargs))
        self._keep(rec, fn, tel)
        return rec

    def hold(self, wrapper, tel) -> Optional[Dict[str, Any]]:
        """A program this process already holds enters THIS recorder
        (module docstring, `held`): `wrapper` is the site's `wrap()` an
        earlier engine made and dispatched.  None, and nothing kept,
        where it carries no record (never dispatched under a live
        recorder: its first dispatch here then reads as any other)."""
        made = getattr(wrapper, "program", None)
        if made is None:
            return None
        rec = dict(made, origin="held", xla_s=0.0, dispatches=0)
        self._keep(rec, wrapper.__wrapped__, tel)
        return rec

    def _keep(self, rec, fn, tel) -> None:
        with self._lock:
            self.programs.append(rec)
            self._program_of[fn] = rec
            top = max(self.programs, key=lambda r: r.get("hbm_bytes", -1))
        if top is rec and "hbm_bytes" in rec:
            tel.gauge("program.temp_bytes", rec["temp_bytes"])
            tel.gauge("program.hbm_bytes", rec["hbm_bytes"])

    def dispatches_by_program(self) -> List[int]:
        """Dispatches of each program record so far, in record order:
        two of these around a search say which programs it ran."""
        with self._lock:
            return [r["dispatches"] for r in self.programs]

    @staticmethod
    def _block(out):
        """Synchronize on the output pytree so the recorded wall covers
        the device work, not just the async dispatch.  A sync cannot
        change values — counts/traces stay bit-identical."""
        try:
            import jax
            return jax.block_until_ready(out)
        except Exception:  # noqa: BLE001 — non-jax outputs pass through
            return out

    @property
    def hbm_peak_bytes(self) -> Optional[int]:
        """The measured device peak (`memory_stats()`), None where the
        backend reports none."""
        from .telemetry import device_mem_high_water
        return device_mem_high_water()

    def dominant_site(self) -> Optional[Tuple[str, float]]:
        """(site name, share) of the site holding the largest wall
        share (wall mode) or dispatch share (cheap mode); None when no
        dispatches were recorded yet.  The watchdog's stall suffix."""
        with self._lock:
            if not self.sites:
                return None
            walls = {n: s.wall_s for n, s in self.sites.items()}
            total = sum(walls.values())
            if total > 0:
                name = max(walls, key=walls.get)
                return name, walls[name] / total
            disp = {n: s.dispatches for n, s in self.sites.items()}
            total = sum(disp.values())
            if total > 0:
                name = max(disp, key=disp.get)
                return name, disp[name] / total
            return None

    # ---- rollup --------------------------------------------------------
    def snapshot(self, force: bool = False) -> Optional[Dict[str, Any]]:
        """The `prof{}` artifact block (schema notes in obs/schema.py).
        None when nothing was recorded and the mode is cheap (so
        un-instrumented artifacts carry no empty noise block) unless
        `force`."""
        with self._lock:
            sites = {n: s.as_dict() for n, s in self.sites.items()}
            programs = [dict(r) for r in self.programs]
        if not force and not sites and self.mode == self.CHEAP:
            return None
        out: Dict[str, Any] = {"mode": self.mode, "sites": sites}
        if programs:
            out["programs"] = programs
        peak = self.hbm_peak_bytes
        if peak is not None:
            out["hbm"] = {"peak_bytes": peak}
        if self.xla_trace_dir:
            out["xla_trace_dir"] = self.xla_trace_dir
        return out


def wrap(name: str, fn, key=None):
    """Register `fn` (typically a jitted callable) as the named
    dispatch site; `key` is the engine's own cache key for it (the
    capacities), kept in its program records.  The active recorder's
    Profiler is resolved at CALL time; with no live recorder
    (NullTelemetry.prof is None) the wrapper is one getattr + a None
    test."""
    def profiled(*args, **kwargs):
        prof = getattr(_cur(), "prof", None)
        if prof is None:
            return fn(*args, **kwargs)
        return prof.record(name, fn, args, kwargs, key, profiled)

    profiled.__wrapped__ = fn
    profiled.program = None  # the newest executable's record
    profiled.__name__ = getattr(fn, "__name__", name)
    profiled.profiler_site = name
    return profiled


# ------------------------------------------------------- rollup helpers

def attribution(summary: Dict[str, Any]) -> Dict[str, Any]:
    """How much of the measured search wall the named sites explain —
    a share of two walls of one run.  Pure dict math (no jax):
    works on any jaxmc.metrics/4 artifact."""
    prof = summary.get("prof") or {}
    sites = prof.get("sites") or {}
    attributed = sum(s.get("wall_s") or 0.0 for s in sites.values())
    search = None
    for ph in summary.get("phases", []) or []:
        if ph.get("name") == "search":
            search = ph.get("wall_s")
            break
    share = (attributed / search) if search else None
    return {"attributed_wall_s": round(attributed, 6),
            "search_wall_s": search,
            "share": None if share is None else round(share, 4)}


# package-namespace aliases (obs.prof_wrap / obs.prof_attribution):
# "wrap" and "attribution" are too generic at the obs level
prof_wrap = wrap
prof_attribution = attribution


def _fmt_bytes(n) -> str:
    if n is None:
        return "-"
    n = float(n)
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024.0 or unit == "TB":
            return f"{n:,.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:,.1f}TB"


def _programs_table(programs: List[Dict[str, Any]], out) -> None:
    """One row per executable: what it holds on a device
    (`memory_analysis()`; hbm = args + out - alias + temp) and whether
    this run compiled it, loaded it, or found it held by its process."""
    if not programs:
        return
    print("programs (one per executable; bytes per device):", file=out)
    w = max(len(str(r.get("site"))) for r in programs)
    print(f"  {'site':<{w}}  {'origin':>8}  {'xla':>8}  {'disp':>6}  "
          f"{'args':>9}  {'out':>9}  {'alias':>9}  {'temp':>9}  "
          f"{'hbm':>9}  key", file=out)
    for r in programs:
        cells = "  ".join(f"{_fmt_bytes(r.get(f)):>9}" for f in (
            "argument_bytes", "output_bytes", "alias_bytes",
            "temp_bytes", "hbm_bytes"))
        print(f"  {str(r.get('site')):<{w}}  {r.get('origin', '-'):>8}  "
              f"{r.get('xla_s', 0.0):7.2f}s  {r.get('dispatches', 0):>6}  "
              f"{cells}  {r.get('key')}", file=out)


def cmd_top(args, out=None) -> int:
    """`python -m jaxmc.obs top FILE` — the per-site table: wall,
    share of the search wall, dispatches, bytes per dispatch,
    recompiles, the host seconds inside its calls (`launch_s`); plus
    the measured device peak, the program records and the compile
    seconds per program (`compile.by_fun`).  Exit 2 when the artifact carries
    no prof block (pre-/4 artifact, or an un-instrumented run)."""
    import json
    import sys
    out = out if out is not None else sys.stdout
    with open(args.file, encoding="utf-8") as fh:
        summary = json.load(fh)
    prof = summary.get("prof")
    if not isinstance(prof, dict) or not (prof.get("sites")
                                          or prof.get("hbm")
                                          or prof.get("programs")):
        print(f"error: {args.file}: no prof block (run with --profile, "
              f"or any telemetry-enabled run on jaxmc.metrics/4+)",
              file=sys.stderr)
        return 2
    sites: Dict[str, Dict[str, Any]] = prof.get("sites") or {}
    att = attribution(summary)
    search = att["search_wall_s"]
    print(f"== prof top: {args.file} (mode={prof.get('mode')})",
          file=out)
    rows: List[Tuple[str, Dict[str, Any]]] = sorted(
        sites.items(),
        key=lambda kv: (-(kv[1].get("wall_s") or 0.0),
                        -kv[1].get("dispatches", 0)))
    if rows:
        w = max(len(n) for n, _ in rows)
        print(f"  {'site':<{w}}  {'wall':>9}  {'share':>6}  "
              f"{'disp':>6}  {'arg/disp':>10}  {'res/disp':>10}  "
              f"{'recomp':>6}  {'launch':>9}", file=out)
        for name, s in rows:
            wall = s.get("wall_s")
            share = (wall / search * 100.0) if wall and search else None
            d = max(s.get("dispatches", 0), 1)
            print(
                f"  {name:<{w}}  "
                f"{'-' if wall is None else f'{wall:9.3f}s'[:10]:>9}  "
                f"{'-' if share is None else f'{share:5.1f}%':>6}  "
                f"{s.get('dispatches', 0):>6}  "
                f"{_fmt_bytes(s.get('arg_bytes', 0) / d if s.get('arg_bytes') else None):>10}  "
                f"{_fmt_bytes(s.get('res_bytes', 0) / d if s.get('res_bytes') else None):>10}  "
                f"{s.get('recompiles', 0):>6}  "
                f"{s.get('launch_s', 0.0):8.4f}s", file=out)
    else:
        print("  (no dispatch sites recorded)", file=out)
    if att["share"] is not None:
        print(f"attributed {att['share'] * 100.0:.1f}% of the search "
              f"wall ({att['attributed_wall_s']:.3f}s of "
              f"{search:.3f}s)", file=out)
    peak = (prof.get("hbm") or {}).get("peak_bytes")
    if peak:
        print(f"hbm: measured peak {_fmt_bytes(peak)}", file=out)
    _programs_table(prof.get("programs") or [], out)
    by_fun = (summary.get("gauges") or {}).get("compile.by_fun") or {}
    if by_fun:
        print("xla compiles by program (a persistent-cache load "
              "counts, at its load time):", file=out)
        w = max(len(n) for n in by_fun)
        for fname, (n, secs) in sorted(by_fun.items(),
                                       key=lambda kv: -kv[1][1]):
            print(f"  {fname:<{w}}  {n:>4}  {secs:9.3f}s", file=out)
    return 0
