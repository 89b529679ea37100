r"""`make backend-check` (ISSUE 11): oracle smoke + per-backend gate.

Two legs, one parseable line each:

  1. ORACLE — the preflight oracle (jaxmc/backend/oracle.py) must find
     at least one live platform inside its deadline (the --smoke
     contract: a broken probe harness fails here, in seconds).
  2. per-platform BASELINE — for every LIVE platform, one small
     jax-backend check leg pinned to it (`python -m jaxmc check
     --backend <plat>`), its jaxmc.metrics artifact gated against that
     platform's OWN saved baseline via `python -m jaxmc.obs diff
     --fail-on-regress` (first run snapshots it — how a new platform's
     baseline is seeded, BASELINE.md "Per-backend baselines").  Dead
     platforms emit `BACKEND-CHECK SKIP <plat>: <reason>` — parseable,
     never a failure — so the same target is green on a cpu-only
     builder box and on a TPU pod.

All live platforms must also agree on the leg's reachable-state counts
(the cross-backend exactness pin; counts differing across XLA targets
would mean the engine layer is NOT backend-portable).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the gate leg: small, repo-local, resident jax engine — big enough to
#: exercise compile + the resident loop, small enough for seconds/leg
_LEG_SPEC = "specs/viewtoy_scaled.tla"
_LEG_MAX_STATES = "4000"


def _run_leg(plat: str, out_dir: str, timeout_s: float) -> dict:
    metrics = os.path.join(out_dir, f"jaxmc_backend_{plat}.json")
    cmd = [sys.executable, "-m", "jaxmc", "check",
           os.path.join(_REPO, _LEG_SPEC),
           "--backend", plat, "--resident", "--no-trace", "--quiet",
           "--max-states", _LEG_MAX_STATES,
           "--metrics-out", metrics]
    env = dict(os.environ, PYTHONPATH=_REPO)
    # the child pins its own platform; a parent-level JAX_PLATFORMS=cpu
    # (tier-1 convention) would override the pin on accelerators
    env.pop("JAX_PLATFORMS", None)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, capture_output=True, text=True,
                           cwd=_REPO, env=env, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"ok": False,
                "error": f"leg timed out after {timeout_s:.0f}s"}
    if p.returncode != 0:
        tail = ((p.stderr or "") + (p.stdout or "")).strip() \
            .splitlines()[-2:] or ["no output"]
        return {"ok": False,
                "error": f"rc={p.returncode}: "
                         + " | ".join(t[:160] for t in tail)}
    try:
        with open(metrics, encoding="utf-8") as fh:
            summary = json.load(fh)
    except (OSError, ValueError) as ex:
        return {"ok": False, "error": f"no metrics artifact ({ex})"}
    res = summary.get("result") or {}
    return {"ok": bool(res.get("ok")), "metrics": metrics,
            "distinct": res.get("distinct"),
            "generated": res.get("generated"),
            "wall_s": round(time.time() - t0, 3)}


#: one-shot cold-start walls excluded from the per-backend phase gate:
#: they time XLA compiles and device init, which swing with box load in
#: a way the measured search window does not (the meshbench legs avoid
#: the problem by gating a WARM timed window; this leg is deliberately
#: cold end-to-end, so it gates states/sec + search instead)
_COLD_PHASES = ("device_init", "engine_build", "layout_sample",
                "compile_arm", "preflight_oracle")


def _gate(metrics_path: str) -> int:
    # per-PLATFORM saved baseline (the artifact name carries the
    # platform): first run snapshots, later runs gate — shared logic
    # with the meshbench legs
    from ..meshbench import _gate as gate
    return gate(metrics_path, log=print, ignore_phases=_COLD_PHASES)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m jaxmc.backend.check",
        description="oracle smoke + per-backend baseline gate")
    ap.add_argument("--out-dir", default=os.environ.get(
        "JAXMC_PROBE_DIR", "/tmp"))
    ap.add_argument("--deadline", type=float, default=float(
        os.environ.get("JAXMC_ORACLE_DEADLINE", "10")))
    ap.add_argument("--leg-timeout", type=float, default=float(
        os.environ.get("JAXMC_BACKEND_CHECK_TIMEOUT", "300")))
    args = ap.parse_args(argv)

    from .oracle import preflight
    v = preflight(deadline_s=args.deadline, use_cache=False)
    for plat, pr in v["probes"].items():
        if pr.get("live"):
            print(f"BACKEND-CHECK oracle {plat} live "
                  f"devices={pr.get('devices')} "
                  f"dispatch={pr.get('dispatch_s')}s")
    if v["platform"] is None:
        print("BACKEND-CHECK FAIL oracle: no live platform "
              f"({v['reason']})", file=sys.stderr)
        return 1
    if v["wall_s"] > args.deadline:
        print(f"BACKEND-CHECK FAIL oracle: preflight took "
              f"{v['wall_s']}s > {args.deadline}s", file=sys.stderr)
        return 1
    print(f"BACKEND-CHECK oracle verdict {v['platform']} "
          f"wall={v['wall_s']}s")

    failures = 0
    counts = {}
    for plat, pr in v["probes"].items():
        if not pr.get("live"):
            print(f"BACKEND-CHECK SKIP {plat}: {pr.get('error')}")
            continue
        r = _run_leg(plat, args.out_dir, args.leg_timeout)
        if not r.get("ok"):
            print(f"BACKEND-CHECK FAIL {plat}: {r.get('error', r)}")
            failures += 1
            continue
        counts[plat] = (r["generated"], r["distinct"])
        print(f"BACKEND-CHECK ok {plat}: {r['generated']} gen / "
              f"{r['distinct']} distinct ({r['wall_s']}s)")
        if _gate(r["metrics"]):
            failures += 1
    if len(set(counts.values())) > 1:
        print(f"BACKEND-CHECK FAIL: live platforms disagree on counts "
              f"{counts}", file=sys.stderr)
        failures += 1
    print(f"backend-check: {'FAIL' if failures else 'ok'} "
          f"({failures} failing legs, "
          f"{len(counts)} live platform(s))")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
