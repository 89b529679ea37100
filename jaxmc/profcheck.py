r"""`make prof-check` (ISSUE 17): the profiler/ledger gate.

Per warm rung (transfer_scaled, symtoy_scaled), three legs — the same
checkpoint-then-resume recipe as the bench-check warmleg, so the timed
window is dispatch-dominated rather than compile-dominated:

  1. WARM      resident run to a truncation checkpoint (no profile);
  2. ON        `--profile` resume to the full cap, metrics artifact
               with a `prof{}` block: the per-site walls must account
               for >= JAXMC_PROF_CHECK_MIN_SHARE (default 0.90) of the
               search phase wall (obs.prof_attribution);
  3. OFF       the identical resume WITHOUT --profile: generated /
               distinct / diameter / ok / truncated must be
               bit-identical to leg 2 — profiling observes the search,
               it never steers it.

Both resume legs append to a TEMP ledger (JAXMC_LEDGER), which is then
gated: `obs history --fail-on-regress` over the real entries must exit
0, and the same gate over a copy with one synthesized degraded entry
(half the observed rate, later timestamp) must exit 1 — the regression
detector is proven live in the same invocation that proves the happy
path.  One parseable `PROF-CHECK …` line per assertion; a jax-less
container prints `PROF-CHECK SKIP …` and exits 0.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import shutil
import subprocess
import sys
import time
from typing import Dict, List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (spec, extra check flags) — repo-local rungs with resident caps
_RUNGS = [
    ("specs/transfer_scaled.tla", []),
    ("specs/symtoy_scaled.tla", ["--no-deadlock"]),
]
_WARM_STATES = 4000
_FULL_STATES = 20000


def _min_share() -> float:
    try:
        return float(os.environ.get("JAXMC_PROF_CHECK_MIN_SHARE", ""))
    except ValueError:
        return 0.90


def _have_jax() -> bool:
    import importlib.util
    return importlib.util.find_spec("jax") is not None


def _check(spec: str, extra: List[str], metrics: Optional[str],
           ledger: Optional[str], timeout_s: float) -> Dict:
    cmd = [sys.executable, "-m", "jaxmc", "check",
           os.path.join(_REPO, spec),
           "--backend", "jax", "--platform", "cpu", "--resident",
           "--no-trace", "--quiet"] + extra
    if metrics:
        cmd += ["--metrics-out", metrics]
    env = dict(os.environ, PYTHONPATH=_REPO, JAX_PLATFORMS="cpu")
    env["JAXMC_LEDGER"] = ledger if ledger else "off"
    t0 = time.time()
    try:
        p = subprocess.run(cmd, capture_output=True, text=True,
                           cwd=_REPO, env=env, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"error": f"leg timed out after {timeout_s:.0f}s"}
    out = {"rc": p.returncode, "stderr": p.stderr,
           "wall_s": round(time.time() - t0, 3)}
    if metrics:
        try:
            with open(metrics, encoding="utf-8") as fh:
                out["summary"] = json.load(fh)
        except (OSError, ValueError) as ex:
            out["error"] = f"no metrics artifact ({ex})"
    return out


def _counts(summary: Dict) -> tuple:
    res = summary.get("result") or {}
    return tuple(res.get(k) for k in
                 ("ok", "generated", "distinct", "diameter",
                  "truncated"))


def _history_rc(ledger: str, extra: Optional[List[str]] = None) -> int:
    """`obs history --fail-on-regress` in-process; output swallowed."""
    from .obs.report import main as obs_main
    buf = io.StringIO()
    import contextlib
    with contextlib.redirect_stdout(buf):
        rc = obs_main(["history", "--ledger", ledger,
                       "--fail-on-regress"] + (extra or []))
    return rc


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m jaxmc.profcheck",
        description="profiler attribution + parity + ledger gate")
    ap.add_argument("--out-dir", default="/tmp")
    ap.add_argument("--leg-timeout", type=float, default=float(
        os.environ.get("JAXMC_PROF_CHECK_TIMEOUT", "600")))
    args = ap.parse_args(argv)

    if not _have_jax():
        print("PROF-CHECK SKIP: no jax in this container")
        return 0
    os.makedirs(args.out_dir, exist_ok=True)
    ledger = os.path.join(args.out_dir, "jaxmc_prof_check_ledger.jsonl")
    if os.path.exists(ledger):
        os.unlink(ledger)  # the gate judges THIS invocation's legs
    failures = 0
    min_share = _min_share()

    from .obs.prof import attribution

    for spec, extra in _RUNGS:
        name = os.path.splitext(os.path.basename(spec))[0]
        ck = os.path.join(args.out_dir, f"jaxmc_prof_check_{name}.ck")
        m_on = os.path.join(args.out_dir,
                            f"jaxmc_prof_check_{name}_on.json")
        m_off = os.path.join(args.out_dir,
                             f"jaxmc_prof_check_{name}_off.json")
        # leg 1: warm checkpoint (excluded from the profiled window)
        r = _check(spec, extra + ["--max-states", str(_WARM_STATES),
                                  "--checkpoint", ck],
                   None, None, args.leg_timeout)
        if r.get("error") or r.get("rc") not in (0, 3):
            print(f"PROF-CHECK FAIL {name} warm leg: rc={r.get('rc')} "
                  f"{r.get('error') or (r.get('stderr') or '')[-200:]}",
                  file=sys.stderr)
            failures += 1
            continue
        # leg 2: profiled resume
        r_on = _check(spec, extra + ["--max-states", str(_FULL_STATES),
                                     "--resume", ck, "--profile"],
                      m_on, ledger, args.leg_timeout)
        # leg 3: identical resume, profile off
        r_off = _check(spec, extra + ["--max-states", str(_FULL_STATES),
                                      "--resume", ck],
                       m_off, ledger, args.leg_timeout)
        bad = [(t, r2) for t, r2 in (("on", r_on), ("off", r_off))
               if r2.get("error") or "summary" not in r2]
        if bad:
            for t, r2 in bad:
                print(f"PROF-CHECK FAIL {name} {t} leg: "
                      f"rc={r2.get('rc')} {r2.get('error') or ''} "
                      f"{(r2.get('stderr') or '')[-200:]}",
                      file=sys.stderr)
            failures += 1
            continue
        s_on, s_off = r_on["summary"], r_off["summary"]
        # parity: profiling must not perturb the search
        if _counts(s_on) != _counts(s_off):
            print(f"PROF-CHECK FAIL {name}: profile-on counts "
                  f"{_counts(s_on)} != profile-off {_counts(s_off)}",
                  file=sys.stderr)
            failures += 1
        else:
            print(f"PROF-CHECK ok {name} parity: counts "
                  f"{_counts(s_on)} bit-identical on/off")
        # attribution: the profiled sites must explain the search wall
        prof = s_on.get("prof")
        if not prof or not prof.get("sites"):
            print(f"PROF-CHECK FAIL {name}: no prof block in the "
                  f"--profile artifact", file=sys.stderr)
            failures += 1
            continue
        att = attribution(s_on)
        share = att.get("share")
        if share is None or share < min_share:
            print(f"PROF-CHECK FAIL {name}: attributed "
                  f"{att.get('attributed_wall_s')}s of "
                  f"{att.get('search_wall_s')}s search wall "
                  f"(share={share}) < {min_share:.0%}",
                  file=sys.stderr)
            failures += 1
        else:
            print(f"PROF-CHECK ok {name} attribution: "
                  f"{share:.0%} of {att['search_wall_s']:.2f}s search "
                  f"wall across {len(prof['sites'])} sites")
        peak = (prof.get("hbm") or {}).get("peak_bytes")
        if peak:  # measured; XLA:CPU reports none
            print(f"PROF-CHECK ok {name} hbm: measured peak "
                  f"{peak:,} bytes")

    # ledger gate: the legs above appended; the real history must pass…
    if not os.path.exists(ledger):
        print("PROF-CHECK FAIL: no ledger entries were appended",
              file=sys.stderr)
        failures += 1
    else:
        rc = _history_rc(ledger)
        if rc != 0:
            print(f"PROF-CHECK FAIL: obs history --fail-on-regress "
                  f"rc={rc} on the fresh ledger", file=sys.stderr)
            failures += 1
        else:
            print("PROF-CHECK ok ledger: history gate green on "
                  "this invocation's entries")
        # …and a synthesized degraded latest entry must trip it
        from .obs import ledger as led
        entries = led.read_entries(ledger)
        rated = [e for e in entries
                 if isinstance(e.get("states_per_sec"), (int, float))]
        if rated:
            worst = dict(rated[-1])
            worst.pop("id", None)
            degraded = led.make_entry(
                worst["rung"], worst["states_per_sec"] * 0.5,
                (worst.get("ts") or time.time()) + 60.0,
                run="degraded", kind=worst.get("kind", "metrics"),
                platform=worst.get("platform"),
                env=worst.get("env"), source="profcheck-synthetic")
            bad_ledger = ledger.replace(".jsonl", "_degraded.jsonl")
            shutil.copyfile(ledger, bad_ledger)
            led.append_entries([degraded], bad_ledger)
            rc2 = _history_rc(bad_ledger)
            if rc2 != 1:
                print(f"PROF-CHECK FAIL: degraded ledger gate rc={rc2}"
                      f" != 1 — regression detector asleep",
                      file=sys.stderr)
                failures += 1
            else:
                print("PROF-CHECK ok ledger: synthesized 2x slowdown "
                      "trips --fail-on-regress (rc 1)")
        else:
            print("PROF-CHECK FAIL: no rated ledger entries to "
                  "synthesize a regression from", file=sys.stderr)
            failures += 1

    print(f"PROF-CHECK {'FAIL' if failures else 'ok'}: "
          f"{failures} failure(s)")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
