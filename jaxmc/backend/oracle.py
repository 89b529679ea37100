r"""Preflight backend oracle (ISSUE 11 tentpole).

`--backend auto` must answer "which live platform should this run use?"
in SECONDS.  The oracle probes each candidate platform with a TINY
representative program (a multi-key sort + a scatter + a vectorized
binary search — the merge kernel's shape in miniature) inside a
TIMEOUT-GUARDED subprocess, so a device that hangs at init costs the
deadline instead of the run (same pattern as compile/cache.py's health
probe).

ONE PROCESS PER CHIP: every probe child initializes the real device,
concurrently, and a chip belongs to one process at a time — on an
exclusive accelerator the children race each other and the parent's own
init that follows.  `auto` picks "the best live platform", i.e. the CPU
when the chip is absent or busy; a run that must be on the chip names
it (`--backend tpu`, which fails loudly without one) and never comes
through here.

Verdict policy: every platform whose probe completes inside its budget
is LIVE; among live platforms the highest rank wins (tpu > gpu > cpu —
the tiny probe's dispatch wall cannot rank real workloads across
platforms, transfer overhead dominates it on accelerators, so the
measured walls are telemetry and tiebreak, not the ranking).
JAXMC_ORACLE_PICK=wall flips to fastest-dispatch-wins for diagnosis.

Telemetry (obs satellite):
  gauge backend.oracle_choice   the chosen platform
  gauge backend.oracle_probe    {platform: {live, compile_s,
                                dispatch_s, devices, error?}}
  gauge backend.oracle_wall_s   total preflight wall

CLI: `python -m jaxmc.backend.oracle [--smoke] [--deadline S]` prints
one parseable `ORACLE <platform> ...` line per candidate plus the
verdict; --smoke exits non-zero when the oracle blows its deadline or
finds no live platform.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional

from . import PLATFORM_RANK

_CANDIDATES = ("tpu", "gpu", "cpu")
_VERDICT_CACHE: Optional[Dict] = None

# the probe program's shape: big enough that a pathologically slow
# backend shows, small enough that cpu-XLA finishes in ~a second
_PROBE_N = 8192

_PROBE_SRC = r"""
import json, sys, time
platform = sys.argv[1]
t_import = time.time()
import jax
jax.config.update("jax_platforms", platform)
import jax.numpy as jnp
from jax import lax
import numpy as np
t_ready = time.time()
try:
    devs = jax.devices()
except Exception as ex:
    print(json.dumps({"ok": False, "error": f"{type(ex).__name__}: {ex}"}))
    sys.exit(0)
N = %(N)d
rng = np.random.RandomState(0)
keys = jnp.asarray(rng.randint(-2**31, 2**31 - 1, (N, 4), dtype=np.int64)
                   .astype(np.int32))
sidx = jnp.arange(N, dtype=jnp.int32)

def probe(keys):
    # the merge kernel in miniature: multi-key sort, rank scatter,
    # fixed-trip binary search — the ops the engines live on
    res = lax.sort(tuple(keys[:, j] for j in range(4)) + (sidx,),
                   num_keys=4, is_stable=True)
    sk = jnp.stack(res[:4], axis=1)
    out = jnp.zeros((N, 4), jnp.int32).at[res[4]].set(sk)
    lo = jnp.zeros(N, jnp.int32)
    hi = jnp.full(N, N, jnp.int32)
    def step(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        row = jnp.take(sk[:, 0], jnp.clip(mid, 0, N - 1))
        lt = row < keys[:, 0]
        return jnp.where(lt, mid + 1, lo), jnp.where(lt, hi, mid)
    lo, _ = lax.fori_loop(0, 14, step, (lo, hi))
    return out.sum() + lo.sum()

jp = jax.jit(probe)
t0 = time.time()
jp(keys).block_until_ready()
compile_s = time.time() - t0
t0 = time.time()
jp(keys).block_until_ready()
dispatch_s = time.time() - t0
print(json.dumps({"ok": True, "devices": len(devs),
                  "platform": devs[0].platform,
                  "compile_s": round(compile_s, 4),
                  "dispatch_s": round(dispatch_s, 4),
                  "import_s": round(t_ready - t_import, 4)}))
""" % {"N": _PROBE_N}


def _parse_probe(p: subprocess.Popen, out: str, err: str,
                 platform: str) -> Dict:
    line = (out or "").strip().splitlines()[-1:] or [""]
    try:
        r = json.loads(line[0])
    except ValueError:
        tail = ((err or "") + (out or "")).strip() \
            .splitlines()[-1:] or ["no output"]
        return {"live": False,
                "error": f"probe rc={p.returncode}: {tail[0][:160]}"}
    if not r.get("ok"):
        return {"live": False, "error": r.get("error", "probe failed")}
    if r.get("platform") != platform:
        # jax silently fell back (e.g. gpu requested, cpu delivered):
        # that platform is NOT live, whatever the probe timing says
        return {"live": False,
                "error": f"jax delivered {r.get('platform')!r} instead"}
    return {"live": True, "devices": r.get("devices"),
            "compile_s": r.get("compile_s"),
            "dispatch_s": r.get("dispatch_s")}


def probe_platforms(platforms: List[str],
                    deadline_s: float = 8.0) -> Dict[str, Dict]:
    """Probe every candidate CONCURRENTLY under one shared deadline:
    the dead platforms' wedge timeouts overlap instead of queueing, so
    the preflight wall is the SLOWEST probe, not the sum (a serial
    sweep measurably blew the 10s budget on a loaded box).  Each probe
    is its own subprocess so a wedged device init costs the deadline,
    never a hung run."""
    from ..obs import context as trace_context
    env = trace_context.child_env()  # probes join the caller's trace
    # children must see every platform: a parent pinned to cpu via
    # JAX_PLATFORMS would make every accelerator probe lie
    env.pop("JAX_PLATFORMS", None)
    t0 = time.time()
    procs: Dict[str, subprocess.Popen] = {}
    out: Dict[str, Dict] = {}
    for plat in platforms:
        try:
            procs[plat] = subprocess.Popen(
                [sys.executable, "-c", _PROBE_SRC, plat],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=env)
        except OSError as ex:
            out[plat] = {"live": False,
                         "error": f"probe could not run: {ex}"}
    for plat, p in procs.items():
        left = max(0.1, deadline_s - (time.time() - t0))
        try:
            so, se = p.communicate(timeout=left)
            out[plat] = _parse_probe(p, so, se, plat)
        except subprocess.TimeoutExpired:
            p.kill()
            p.communicate()
            out[plat] = {"live": False,
                         "error": f"probe wedged past "
                                  f"{deadline_s:.1f}s "
                                  f"(device hung at init?)"}
    return out


def probe_platform(platform: str, timeout_s: float = 8.0) -> Dict:
    """One candidate's probe result: {live, compile_s?, dispatch_s?,
    devices?, error?} (the single-platform convenience wrapper)."""
    return probe_platforms([platform], deadline_s=timeout_s)[platform]


def preflight(deadline_s: float = 10.0, tel=None,
              candidates: Optional[List[str]] = None,
              use_cache: bool = True) -> Dict:
    """Probe the candidate platforms and pick the best live one.

    Returns {"platform": str | None, "probes": {plat: probe},
    "wall_s": float, "reason": str}.  The verdict is cached per process
    (serve daemons and repeated sessions must not re-pay the probes);
    `use_cache=False` forces a fresh sweep."""
    global _VERDICT_CACHE
    if use_cache and _VERDICT_CACHE is not None:
        return _VERDICT_CACHE
    from .. import obs
    tel = tel if tel is not None else obs.current()
    cands = list(candidates or _CANDIDATES)
    t0 = time.time()
    # probe budget leaves 2s of the deadline for subprocess spawn +
    # result collection: a wedged-platform probe costs its full budget,
    # and measured spawn overhead on a loaded 2-core box reaches ~1.5s
    budget = float(os.environ.get("JAXMC_ORACLE_PROBE_TIMEOUT",
                                  str(max(1.0, deadline_s - 2.0))))
    probes = probe_platforms(cands, deadline_s=budget)
    live = [p for p in cands if probes[p].get("live")]
    pick_by_wall = os.environ.get("JAXMC_ORACLE_PICK") == "wall"
    if not live:
        choice, reason = None, "no live platform (all probes failed)"
    elif pick_by_wall:
        choice = min(live,
                     key=lambda p: probes[p].get("dispatch_s") or 1e9)
        reason = "fastest probe dispatch (JAXMC_ORACLE_PICK=wall)"
    else:
        choice = max(live, key=lambda p: PLATFORM_RANK.get(p, 0))
        reason = f"highest-ranked live platform of {live}"
    wall = round(time.time() - t0, 3)
    verdict = {"platform": choice, "probes": probes, "wall_s": wall,
               "reason": reason}
    tel.gauge("backend.oracle_choice", choice or "none")
    tel.gauge("backend.oracle_probe", probes)
    tel.gauge("backend.oracle_wall_s", wall)
    tel.event("backend.oracle", choice=choice, wall_s=wall,
              reason=reason)
    _VERDICT_CACHE = verdict
    return verdict


def reset_cache_for_tests() -> None:
    global _VERDICT_CACHE
    _VERDICT_CACHE = None


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m jaxmc.backend.oracle",
        description="probe visible platforms, pick the best live one")
    ap.add_argument("--deadline", type=float, default=float(
        os.environ.get("JAXMC_ORACLE_DEADLINE", "10")))
    ap.add_argument("--smoke", action="store_true",
                    help="exit 1 unless a live platform was chosen "
                         "inside the deadline")
    args = ap.parse_args(argv)
    v = preflight(deadline_s=args.deadline, use_cache=False)
    for plat, pr in v["probes"].items():
        if pr.get("live"):
            print(f"ORACLE {plat} live devices={pr['devices']} "
                  f"compile={pr['compile_s']}s "
                  f"dispatch={pr['dispatch_s']}s")
        else:
            print(f"ORACLE {plat} SKIP: {pr.get('error')}")
    print(f"ORACLE verdict {v['platform'] or 'none'} "
          f"wall={v['wall_s']}s ({v['reason']})")
    if args.smoke:
        if v["platform"] is None:
            print("ORACLE FAIL: no live platform", file=sys.stderr)
            return 1
        if v["wall_s"] > args.deadline:
            print(f"ORACLE FAIL: preflight took {v['wall_s']}s "
                  f"> deadline {args.deadline}s", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
