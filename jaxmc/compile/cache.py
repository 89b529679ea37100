r"""Persistent XLA compilation-cache wiring: ONE resolver, one enabler.

Where the cache lives (`resolve_cache_dir`, used by every call site —
`check`, the serve device owner, the corpus sweep, the harnesses):

  JAX_COMPILATION_CACHE_DIR set   the cache is THERE.  jax reads that
                                  variable itself; jaxmc never writes
                                  the directory knob, and never renames
                                  or moves the directory.
  unset                           `<checkout>/.jax_cache` (gitignored).

No temp dir, pid or clock value appears in any cache or profile path:
the path is part of what makes a second process hit, so a cache that
moves never hits.  Capacity profiles (below) live INSIDE the resolved
directory (`<cache>/profiles/` — jax looks entries up by exact
filename, so a subdirectory is invisible to it), because a learned-caps
file is what turns the resident engine's growth recompiles into zero
and must travel with the compiled programs it belongs to.

Every device-backend run uses the cache.  JAXMC_COMPILE_CACHE=0|off|none
is the opt-out (the CPU test suite sets it: tests/conftest.py records
why XLA:CPU blob reloads are kept out of the tests).

`enable_guarded_cache` wraps the enable in a guard battery — every
step fails COLD, never broken: a cache problem degrades to cold
compilation with a named reason, it cannot fail or hang the run:

  1. build fingerprint: `<dir>/jaxmc.cache.meta.json` records
     {python, jax, machine}.  A mismatch is the cross-build reload-hang
     class: this process compiles cold and SAYS SO (remove the
     directory to start a fresh cache) — the directory is not touched.
  2. corruption scan: zero-length `*-cache` entries and stale `*.tmp`
     writer droppings are moved into `<dir>/.quarantine/` and the cache
     continues — one bad entry never disables the cache.
  3. health probe: a SUBPROCESS (pinned to CPU, so it never contends
     for an exclusive accelerator) jits a trivial program against the
     dir under a hard timeout (JAXMC_CACHE_GUARD_TIMEOUT, default
     60 s).  A wedge or crash falls back cold.  The probe result is
     stamped (`<dir>/jaxmc.cache.probe.ok`) so a round of processes
     pays for it ONCE (JAXMC_CACHE_PROBE=0 skips it entirely).

Effectiveness is exposed as obs counters:

  compile.persistent_cache_hits    (jax monitoring event
                                    '/jax/compilation_cache/cache_hits')
  gauge compile.persistent_cache_dir
  gauge compile.persistent_cache_entries_start / _end
  gauge compile.persistent_cache_guard   ("ok[...]" | "cold-fallback:..")
  counter compile.persistent_cache_fallbacks / _quarantines
  counter compile.xla_compiles / compile.xla_compile_s, and the same by
  program: gauge compile.by_fun {fun_name: [compiles, seconds]}

Fault sites (jaxmc/faults.py, chaos suite): `cache_hang` wedges the
health probe, `cache_corrupt` zero-truncates one entry before the scan.
tests/test_cache_guard.py pins that each one degrades to cold
compilation with the run intact.

The persistent cache spares the COMPILE; a new engine still traces,
lowers and loads.  The program registry (ISSUE 37, end of this file)
spares those too where the process already holds the program:
`held_program` keys an engine's jitted programs by a signature of
everything their trace reads (`canonical`, `TpuExplorer._program_sig`).
  counter compile.program_hits / compile.program_misses / _unkeyed
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Optional, Tuple

_OFF_VALUES = ("0", "off", "none", "disabled")

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_META_NAME = "jaxmc.cache.meta.json"
_PROBE_STAMP = "jaxmc.cache.probe.ok"
_PROBE_FRESH_S = 3600.0  # one probe per dir per hour, not per process


def checkout_cache_dir() -> str:
    """The cache's home when nobody placed it from outside."""
    return os.path.join(_REPO, ".jax_cache")


def resolve_cache_dir() -> str:
    """THE cache location (module docstring): where
    JAX_COMPILATION_CACHE_DIR says, else `<checkout>/.jax_cache`."""
    return os.environ.get(_CACHE_ENV) or checkout_cache_dir()


def cache_disabled_by_env() -> bool:
    """True when JAXMC_COMPILE_CACHE opts OUT (0/off/none)."""
    d = os.environ.get("JAXMC_COMPILE_CACHE")
    return d is not None and d.strip().lower() in _OFF_VALUES


_LISTENER_REGISTERED = False
_BY_FUN_LOCK = threading.Lock()  # compiles run on any thread


def _count_entries(path: str) -> Optional[int]:
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except OSError:
        return None


def _fingerprint() -> dict:
    """The build identity whose mismatch marks a foreign cache (the
    cross-build reload-hang class). jax import only — no device init."""
    import platform
    fp = {"python": platform.python_version(),
          "machine": platform.machine()}
    try:
        import jax
        fp["jax"] = jax.__version__
    except Exception:  # noqa: BLE001 — fingerprint must never raise
        fp["jax"] = "unavailable"
    return fp


def _guard(path: str, timeout_s: float, tel) -> Tuple[bool, str]:
    """Run the guard battery over `path`. Returns (enable?, detail)."""
    from .. import faults
    os.makedirs(path, exist_ok=True)
    notes = []

    # -- step 1: build fingerprint ----------------------------------
    meta_path = os.path.join(path, _META_NAME)
    fp = _fingerprint()
    try:
        with open(meta_path) as fh:
            old = json.load(fh)
        if old != fp:
            return False, (f"cache written by another build ({old}) — "
                           f"compiling cold; remove {path} to start a "
                           f"fresh cache")
    except FileNotFoundError:
        try:
            tmp = meta_path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(fp, fh)
            os.replace(tmp, meta_path)
        except OSError:
            pass  # another process won the race; theirs matches or
            # the next enable falls back cold
    except (OSError, ValueError):
        return False, (f"unreadable cache fingerprint — compiling cold; "
                       f"remove {path} to start a fresh cache")

    # -- step 2: corruption scan ------------------------------------
    # chaos site: damage one entry right before the scan so the test
    # harness can pin "detected, quarantined, run continues"
    if faults.fire("cache_corrupt") is not None:
        victims = [n for n in os.listdir(path) if n.endswith("-cache")]
        victim = os.path.join(
            path, victims[0] if victims else "poisoned-entry-cache")
        try:
            with open(victim, "w"):
                pass  # zero-truncate (or create empty): detectably bad
        except OSError:
            pass
    qdir = os.path.join(path, ".quarantine")
    bad = 0
    try:
        now = time.time()
        for name in os.listdir(path):
            p = os.path.join(path, name)
            if not os.path.isfile(p):
                continue
            try:
                st = os.stat(p)
            except OSError:
                continue
            is_bad = (name.endswith("-cache") and st.st_size == 0) or \
                (name.endswith(".tmp") and now - st.st_mtime > 3600)
            if is_bad:
                try:
                    os.makedirs(qdir, exist_ok=True)
                    os.rename(p, os.path.join(qdir, name))
                    bad += 1
                except OSError:
                    pass
    except OSError:
        pass
    if bad:
        tel.counter("compile.persistent_cache_quarantines", bad)
        notes.append(f"quarantined {bad} corrupt entr"
                     f"{'y' if bad == 1 else 'ies'}")

    # -- step 3: health probe under a hard timeout ------------------
    if os.environ.get("JAXMC_CACHE_PROBE", "1") != "0":
        stamp = os.path.join(path, _PROBE_STAMP)
        fresh = False
        try:
            fresh = time.time() - os.path.getmtime(stamp) < _PROBE_FRESH_S
        except OSError:
            pass
        if not fresh:
            ok, why = _health_probe(path, timeout_s)
            if not ok:
                return False, f"health probe failed ({why})"
            try:
                with open(stamp, "w") as fh:
                    fh.write("ok\n")
            except OSError:
                pass
            notes.append("probed ok")

    return True, "; ".join(notes) if notes else "ok"


def _health_probe(path: str, timeout_s: float) -> Tuple[bool, str]:
    """Jit one trivial program against the cache dir in a SUBPROCESS so
    a wedged blob reload (the known failure class) hits OUR timeout, not
    the run's deadline. The child is pinned to CPU — it must never ask
    for an accelerator its parent is about to own — and is handed the
    dir the way every process is: through JAX_COMPILATION_CACHE_DIR.
    The `cache_hang` fault site wedges the child."""
    code = (
        "import os, sys, time\n"
        "sys.path.insert(0, " + repr(_REPO) + ")\n"
        "from jaxmc import faults\n"
        "if faults.fire('cache_hang') is not None:\n"
        "    time.sleep(3600)  # the simulated wedge\n"
        "import jax\n"
        "import jax.numpy as jnp\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(3)).block_until_ready()"
        "\n")
    try:
        from ..obs import context as trace_context
        p = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True,
                           timeout=timeout_s,
                           env=dict(trace_context.child_env(),
                                    JAX_PLATFORMS="cpu",
                                    **{_CACHE_ENV: path}))
    except subprocess.TimeoutExpired:
        return False, f"wedged past {timeout_s:.0f}s"
    except OSError as ex:
        return False, f"probe could not run: {ex}"
    if p.returncode != 0:
        tail = (p.stderr or "").strip().splitlines()[-1:] or ["?"]
        return False, f"probe rc={p.returncode}: {tail[0][:120]}"
    return True, "ok"


def enable_guarded_cache(tel=None, timeout_s: Optional[float] = None
                         ) -> Optional[str]:
    """Run the guard battery over the resolved cache dir, then enable
    the cache there (call with jax importable, before the first
    compile).  Returns the cache dir when enabled, None on opt-out or
    cold fallback.  Never hangs: every guard defect degrades to cold
    compilation."""
    from .. import obs
    if tel is None:
        tel = obs.current()
    _register_listeners()
    if cache_disabled_by_env():
        _compile_cold()
        tel.gauge("compile.persistent_cache_guard",
                  "disabled:JAXMC_COMPILE_CACHE opt-out")
        return None
    path = resolve_cache_dir()
    if timeout_s is None:
        timeout_s = float(os.environ.get("JAXMC_CACHE_GUARD_TIMEOUT",
                                         "60"))
    try:
        ok, detail = _guard(path, timeout_s, tel)
    except Exception as ex:  # noqa: BLE001 — guard bugs degrade cold
        ok, detail = False, f"guard error: {type(ex).__name__}: {ex}"
    if not ok:
        _compile_cold()
        tel.gauge("compile.persistent_cache_guard",
                  f"cold-fallback:{detail}")
        tel.counter("compile.persistent_cache_fallbacks")
        return None
    _enable(path, tel)
    tel.gauge("compile.persistent_cache_guard",
              f"ok ({detail})" if detail != "ok" else "ok")
    return path


def _compile_cold() -> None:
    """Make an opt-out or a cold fallback REAL: with
    JAX_COMPILATION_CACHE_DIR set jax would read the directory on its
    own."""
    import jax
    jax.config.update("jax_enable_compilation_cache", False)


def _enable(path: str, tel) -> None:
    """Point jax's persistent compilation cache at `path` — a no-op on
    the directory knob when the environment already did."""
    import jax
    if os.environ.get(_CACHE_ENV) != path:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    # cache everything: the per-arm kernels are small but numerous,
    # and the default min-compile-time floor would skip most of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    tel.gauge("compile.persistent_cache_dir", path)
    n0 = _count_entries(path)
    if n0 is not None:
        tel.gauge("compile.persistent_cache_entries_start", n0)


def _register_listeners() -> None:
    """Mirror jax's compile monitoring into the active obs telemetry:
    every XLA backend compile (count + seconds — set-up cost, reported
    beside a run, never a metric of record) and every persistent-cache
    event.  Registered exactly once per process: jax.monitoring keeps
    every listener, so a second enable call (the serve owner enables
    per session) would double-count."""
    global _LISTENER_REGISTERED
    if _LISTENER_REGISTERED:
        return
    from jax import monitoring

    # both route through current() at fire time: the telemetry active
    # when the compile runs, not when the cache was enabled
    def _on_event(event: str, **kw) -> None:
        if "compilation_cache" not in event:
            return
        from .. import obs as _obs
        name = event.rsplit("/", 1)[-1]  # e.g. 'cache_hits'
        if name.startswith("cache_"):
            name = name[len("cache_"):]
        _obs.current().counter(f"compile.persistent_cache_{name}")

    def _on_duration(event: str, secs: float, fun_name: str = "?",
                     **kw) -> None:
        if not event.endswith("/backend_compile_duration"):
            return
        from .. import obs as _obs
        tel = _obs.current()
        tel.counter("compile.xla_compiles")
        tel.counter("compile.xla_compile_s", secs)
        # the same seconds by program (jax names it `jit(<function>)`):
        # which of a first contact's programs the time went to
        if fun_name.startswith("jit(") and fun_name.endswith(")"):
            fun_name = fun_name[4:-1]
        if tel.enabled:
            with _BY_FUN_LOCK:
                table = tel.gauges.get("compile.by_fun") or {}
                n, total = table.get(fun_name, (0, 0.0))
                # gauge() installs a NEW table: a snapshot that a scrape
                # thread holds is never written to
                tel.gauge("compile.by_fun",
                          {**table, fun_name: [n + 1, total + secs]})

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _LISTENER_REGISTERED = True


def record_entries_end(path: Optional[str], tel=None) -> None:
    """Stamp the end-of-run entry count (a second identical run shows
    entries_start == entries_end AND persistent_cache_hits > 0)."""
    if not path:
        return
    from .. import obs
    n = _count_entries(path)
    if n is not None:
        (tel if tel is not None else obs.current()).gauge(
            "compile.persistent_cache_entries_end", n)


# ---------------------------------------------------------------------
# Learned per-spec CAPACITY PROFILES (ISSUE 6).
#
# The resident engine's capacity buckets (SC/FCap/AccCap/VC) are learned
# by overflow-growth — and every growth is a full XLA recompile of the
# whole while_loop program, potentially inside somebody's measured
# window.  A capacity profile persists the caps a completed resident run
# ended with, keyed by (module, layout signature), INSIDE the compile
# cache dir (profile_dir): the next run on the same spec starts at the learned caps, its
# one warm-up compile covers the whole run, and `window_recompiles`
# reads 0 in the steady-state bench.
#
# Safety: a profile is a pure PERFORMANCE hint — wrong caps can only
# cost a recompile (the engine's overflow-growth path still works), so a
# stale/foreign profile is IGNORED with a named reason, never trusted
# into a crash.  Validation: schema, module name, layout signature (it
# covers the lane plan, so a packing change invalidates profiles), and
# sane positive-int caps.  JAXMC_CAP_PROFILE=0 disables load AND save.

_PROFILE_SCHEMA = "jaxmc.capacity-profile/1"
_PROFILE_CAP_KEYS = ("SC", "FCap", "AccCap", "VC")


def profiles_enabled() -> bool:
    return os.environ.get("JAXMC_CAP_PROFILE", "1").strip().lower() \
        not in _OFF_VALUES


def profile_dir() -> str:
    d = os.environ.get("JAXMC_PROFILE_STORE")
    if d:
        return d
    return os.path.join(resolve_cache_dir(), "profiles")


def profile_path(module: str, layout_sig: str, variant: str = "") -> str:
    safe = "".join(ch if ch.isalnum() or ch in "._-" else "_"
                   for ch in module)[:80]
    vtag = ""
    if variant:
        vsafe = "".join(ch if ch.isalnum() or ch in "._-" else "_"
                        for ch in variant)[:40]
        vtag = f".{vsafe}"
    return os.path.join(profile_dir(),
                        f"{safe}.{layout_sig[:16]}{vtag}.json")


def load_capacity_profile(module: str, layout_sig: str, tel=None,
                          variant: str = "",
                          keys: Tuple[str, ...] = _PROFILE_CAP_KEYS,
                          optional: Tuple[str, ...] = ()
                          ) -> Optional[dict]:
    """The validated caps dict, or None with a NAMED degrade reason in
    the `profile.status` gauge (absent / unreadable / foreign schema /
    module mismatch / stale layout / bad caps).  Never raises.

    `variant` keys engine families apart: the resident single-chip
    engine stores the default variant, the mesh engine stores one
    profile per (device count, exchange strategy) — `mesh-d4-a2a` —
    because its capacity shape (per-SHARD seen/frontier, trace-ring
    levels, the a2a bucket factor) depends on D (ISSUE 8).  `keys`
    names the cap fields that variant persists."""
    from .. import obs
    tel = tel if tel is not None else obs.current()
    if not profiles_enabled():
        tel.gauge("profile.status", "disabled:JAXMC_CAP_PROFILE")
        return None
    path = profile_path(module, layout_sig, variant)

    def _no(reason: str) -> None:
        tel.gauge("profile.status", f"degraded:{reason}")
        tel.counter("profile.degrades")

    try:
        with open(path, encoding="utf-8") as fh:
            p = json.load(fh)
    except FileNotFoundError:
        tel.gauge("profile.status", "absent")
        return None
    except (OSError, ValueError) as ex:
        _no(f"unreadable profile ({type(ex).__name__})")
        return None
    if not isinstance(p, dict) or p.get("schema") != _PROFILE_SCHEMA:
        _no(f"foreign schema {p.get('schema') if isinstance(p, dict) else type(p).__name__!r}")
        return None
    if p.get("module") != module:
        _no(f"module mismatch ({p.get('module')!r})")
        return None
    if p.get("layout_sig") != layout_sig:
        # the one expected staleness class: the model/bounds/pack plan
        # changed since the profile was learned
        _no("stale layout signature (model, caps or packing changed)")
        return None
    if p.get("variant", "") != variant:
        _no(f"variant mismatch ({p.get('variant')!r})")
        return None
    caps = p.get("caps")
    if not isinstance(caps, dict) or not all(
            isinstance(caps.get(k), int) and 0 < caps[k] < (1 << 31)
            for k in keys):
        _no("malformed caps")
        return None
    tel.gauge("profile.status", "loaded")
    tel.counter("profile.hits")
    out = {k: int(caps[k]) for k in keys}
    # `optional` names caps newer engines persist but older profiles
    # may lack (the mesh VC): validated the same way when present,
    # silently absent otherwise
    for k in optional:
        if isinstance(caps.get(k), int) and 0 < caps[k] < (1 << 31):
            out[k] = int(caps[k])
    return out


def save_capacity_profile(module: str, layout_sig: str,
                          caps: dict, tel=None, variant: str = "",
                          keys: Tuple[str, ...] = _PROFILE_CAP_KEYS,
                          optional: Tuple[str, ...] = (),
                          **extra) -> Optional[str]:
    """Persist the caps a completed resident run ended with (atomic
    write; max-merged over any existing valid profile so alternating
    workloads never thrash each other downward).  Never raises.
    `optional` caps persist when the run learned them and are dropped
    (without vetoing the save) when it did not."""
    from .. import obs
    tel = tel if tel is not None else obs.current()
    if not profiles_enabled():
        return None
    try:
        prev = load_capacity_profile(module, layout_sig,
                                     tel=obs.NullTelemetry(),
                                     variant=variant, keys=keys,
                                     optional=optional)
        merged = {k: int(caps[k]) for k in keys
                  if isinstance(caps.get(k), int)}
        if len(merged) != len(keys):
            return None
        for k in optional:
            if isinstance(caps.get(k), int):
                merged[k] = int(caps[k])
        if prev:
            for k in list(merged):
                if k in prev:
                    merged[k] = max(merged[k], prev[k])
            for k in optional:
                if k in prev and k not in merged:
                    merged[k] = prev[k]
        d = profile_dir()
        os.makedirs(d, exist_ok=True)
        path = profile_path(module, layout_sig, variant)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump({"schema": _PROFILE_SCHEMA, "module": module,
                       "layout_sig": layout_sig, "variant": variant,
                       "caps": merged,
                       "build": _fingerprint(), "saved_at": time.time(),
                       **extra}, fh)
        os.replace(tmp, path)
        tel.gauge("profile.status", "saved")
        tel.counter("profile.saves")
        return path
    except Exception:  # noqa: BLE001 — a profile is a hint, never a crash
        return None



# ------------------------------------------------------------------
# The program registry (ISSUE 37).
#
# A served edit that leaves the model unchanged is a new content hash,
# so a new session and a new engine, whose jitted programs are new
# `jax.jit` objects: jax traces the while_loop program again, lowers
# it, hashes the module and loads the executable from the persistent
# cache — seconds of host time with the chip idle, for a program the
# process has dispatched before.  The registry keeps, process-wide, the
# jitted callables engines made, under (site, program signature, the
# site's own key).  An engine whose own cache misses asks here before
# it makes a new `jax.jit`; on a hit it dispatches the callable an
# earlier engine made and jax's fast path finds the executable by the
# function's identity: no trace, no lowering, no cache key, no load.
#
# Soundness rests on the SIGNATURE covering everything the trace reads
# (`TpuExplorer._program_sig`): a wrong hit would answer one spec with
# another's program.  So `canonical` FAILS CLOSED — anything it cannot
# render without an address or an iteration order gives no signature,
# and an engine without one keeps its own jits, exactly the path before
# the registry (counter `compile.program_unkeyed`).
#
# What an entry pins on the host: the prof-wrapped jitted callable (and
# on it the program record, `wrapper.program`), jax's executable for
# it, and what the traced closures hold — the first engine's kernel
# closures with their KernelCtx (model, layout, bounds), lane plan and
# predicate kernels; NOT the engine (bfs.py hands its sites
# `_keys_fn()`, free of `self`).  Bounded, least recently used out
# first; no environment variable, no option.

_PROGRAMS_MAX = 64
_PROGRAMS: "OrderedDict[tuple, Callable]" = OrderedDict()
_PROGRAMS_LOCK = threading.Lock()  # the in-process serve path builds
# engines on worker threads


class Unrenderable(Exception):
    """`canonical` met a value it cannot render canonically."""


def canonical(v: Any) -> Any:
    """`v` as nested tuples of str / int / bool / None whose `repr` is
    the same for equal values in every process and at every address:
    AST nodes (`front/tla_ast.py`: frozen dataclasses, no source
    positions) by class and fields, sets and dicts sorted, model values
    and built-ins by name, definitions (`OpClosure`) by name, params,
    body and captured bindings.  Raises `Unrenderable` for anything
    else — a function, an object whose `repr` holds an address, a
    lazily materialized value, a cycle."""
    import dataclasses

    from ..front import tla_ast as A
    from ..front.cfg import CfgModelValue, ModelConfig
    from ..sem.eval import BuiltinOp, OpClosure
    from ..sem.modules import InstanceNamespace, LoadedModule
    from ..sem.values import Fcn, FcnSetV, InfiniteSet, ModelValue

    open_: set = set()  # ids on the path from the root: a cycle's guard

    def unordered(items):
        return tuple(sorted((walk(x) for x in items), key=repr))

    def render(v):
        t = type(v)
        if t in (tuple, list):
            return ("seq",) + tuple(walk(x) for x in v)
        if t in (frozenset, set):
            return ("set",) + unordered(v)
        if t is dict:
            return ("map",) + unordered(v.items())
        if t in (ModelValue, CfgModelValue):
            return ("mv", v.name)
        if t is Fcn:
            return ("fcn",) + unordered(v.d.items())
        if t is InfiniteSet:
            return ("inf", v.kind, walk(v.param))
        if t is FcnSetV:
            return ("fcnset", walk(v.dom), walk(v.rng))
        if t is BuiltinOp:
            return ("builtin", v.name)
        if t is OpClosure:
            return ("op", v.name, walk(v.params), walk(v.body),
                    walk(v.bound), walk(v.defs))
        if t is InstanceNamespace:
            return ("instance", walk(v.module), walk(v.substs),
                    walk(v.params))
        if t is LoadedModule:
            # never its path: a stamped copy lives somewhere else
            return ("module", v.name, walk(v.ast.extends), walk(v.defs),
                    walk(v.constants), walk(v.variables))
        if isinstance(v, A.Node) or t is ModelConfig:
            return (t.__name__,) + tuple(
                walk(getattr(v, f.name)) for f in dataclasses.fields(v))
        raise Unrenderable(f"a {t.__name__}")

    def walk(v):
        if v is None or type(v) in (bool, int, str, float):
            return v
        if id(v) in open_:
            raise Unrenderable(f"cycle through a {type(v).__name__}")
        open_.add(id(v))
        try:
            return render(v)
        finally:
            open_.discard(id(v))

    return walk(v)


def model_canonical(model) -> Any:
    """Everything of a loaded `Model` (`sem/modules.py`) that an
    engine's kernels are built from: the definition table with the
    cfg's constants bound, the checked formulas, the module's name and
    what it extends — and not where its files are."""
    m = model
    return canonical((
        "model", m.module.name, m.module.ast.extends, m.module.constants,
        m.vars, m.cfg, m.defs, m.init, m.next, m.invariants,
        m.constraints, m.action_constraints, m.properties, m.symmetry,
        m.view, bool(m.check_deadlock), m.fairness))


def held_program(site: str, sig: Optional[str], key,
                 make: Callable[[], Callable]) -> Callable:
    """The jitted program of `site` for (`sig`, `key`): the one this
    process already holds, else `make()`'s, kept for the next engine.
    `sig` None (the engine could not sign what its trace reads): always
    `make()`'s, and nothing is kept.  A hit tells the active recorder
    what a new executable would have (`Profiler.hold`: the program
    record with origin "held", the `program.*` gauges) and leaves
    `compile.xla_compile_s` in its counters, at 0.0 if nothing loads."""
    from .. import obs
    tel = obs.current()
    if sig is None:
        tel.counter("compile.program_unkeyed")
        return make()
    rk = (site, sig, key)
    with _PROGRAMS_LOCK:
        fn = _PROGRAMS.get(rk)
        hit = fn is not None
        if hit:
            _PROGRAMS.move_to_end(rk)
        else:
            # `make` builds closures and a `jax.jit` object: no trace,
            # so the lock is held for microseconds, and two threads
            # asking for one key get one callable
            fn = _PROGRAMS[rk] = make()
            while len(_PROGRAMS) > _PROGRAMS_MAX:
                _PROGRAMS.popitem(last=False)
    tel.counter("compile.program_hits" if hit
                else "compile.program_misses")
    if hit:
        tel.counter("compile.xla_compile_s", 0.0)
        prof = getattr(tel, "prof", None)
        if prof is not None:
            prof.hold(fn, tel)
    return fn


def forget_programs() -> None:
    """Empty the registry (tests; a process that changed what a trace
    reads behind the signature's back)."""
    with _PROGRAMS_LOCK:
        _PROGRAMS.clear()
