r"""Multi-chip mesh bench + parity harness: `python -m jaxmc.meshbench`.

ISSUE 8 closes ROADMAP item 1's measurement gap: the mesh-sharded
engine (tpu/mesh.py — owner-routed a2a dedup, device-resident level
loop) needs (a) PARITY legs proving bit-identical counts against the
manifest pins at several device counts, and (b) a SCALING CURVE
(states/sec/chip over D) published as a MULTICHIP_r* artifact.  Both
run per-D in fresh subprocesses because the device count is fixed at
jax init: each child forces `XLA_FLAGS=--xla_force_host_platform_
device_count=D` virtual CPU devices.

What this module still is (ISSUE 26): a CPU PARITY / SCALING GATE.
`check` and `bench` are test gates, not chip paths:
JAXMC_MESHBENCH_PLATFORM defaults to "cpu", and what they time on
virtual devices is never a device metric.  The chip path of the sharded
engine is the normal one — `python -m jaxmc check --devices N`
(session.py builds MeshExplorer), which chip_smoke.py's mesh leg and the
benchmark cell `mesh-recheck-4p` drive; nothing on the chip goes through
here any more.  `child` keeps building its engine by hand because its
legs sweep D = 1 (the mesh engine on ONE device, ROADMAP C1), which
`SessionConfig(devices=1)` by contract does not build, and pin
`exchange` per leg, which is no session option.

Subcommands
  check   D in {2,4} (default) parity legs over the repo-local rungs
          (viewtoy_scaled / symtoy_scaled + MCraft_micro when the
          reference corpus is mounted): counts must equal the corpus
          manifest pins, host_syncs may never exceed the level count
          (it counts SUPERSTEPS since ISSUE 10, so it is usually well
          below), and each leg's jaxmc.metrics/2 artifact gates like
          every bench-check leg via
          `python -m jaxmc.obs diff --fail-on-regress` against a saved
          baseline (first run snapshots it).  Wired into
          `make bench-check` via `make multichip-check`.
  bench   D in {1,2,4,8} (default) timed legs over the bench rungs
          (MCraft_3s_bench + transfer_scaled): per D, one warm-up run
          (compile + capacity training + profile persist) then a timed
          fully-warm run — states/sec/chip, per-level exchange bytes,
          shard balance, host_syncs <= levels (supersteps working)
          and window_recompiles (must be 0 on the warm run).  Writes
          the MULTICHIP_r* artifact (--out) plus per-leg metrics
          artifacts, gated the same way when baselines exist; two
          MULTICHIP_r* artifacts diff directly via
          `python -m jaxmc.obs diff`.
  child   one (spec, D) leg — internal.

Rungs that need the reference corpus (the MCraft family EXTENDS the
reference raft.tla) emit a parseable `MESHBENCH SKIP` line in builder
containers instead of failing (ISSUE 6).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_RESULT_TAG = "MESHBENCH_RESULT "

# the default rung sets (spec paths relative to the repo root; cfg
# auto-discovered next to the spec unless given)
CHECK_RUNGS = [
    ("specs/viewtoy_scaled.tla", None),
    ("specs/symtoy_scaled.tla", None),
    ("specs/MCraftMicro.tla", "specs/MCraft_micro.cfg"),
]
BENCH_RUNGS = [
    ("specs/MCraftMicro.tla", "specs/MCraft_3s_bench.cfg"),
    ("specs/transfer_scaled.tla", None),
]


def _needs_reference(spec: str, cfg: Optional[str]) -> Optional[str]:
    """A SKIP reason when this rung cannot load in this container."""
    from .corpus import REFERENCE, case_for_cfg
    cfgb = os.path.basename(cfg) if cfg else \
        os.path.basename(os.path.splitext(spec)[0] + ".cfg")
    case = case_for_cfg(cfgb)
    needs = case is not None and (case.root == "ref" or case.includes)
    if needs and not os.path.isdir(os.path.join(REFERENCE, "examples")):
        return (f"reference corpus not mounted at {REFERENCE} "
                f"(driver environment only)")
    return None


def _leg_name(spec: str, cfg: Optional[str]) -> str:
    base = os.path.splitext(os.path.basename(cfg or spec))[0]
    return base


def _run_child(spec: str, cfg: Optional[str], devices: int,
               exchange: Optional[str], timed: bool, out_dir: str,
               store_trace: bool, timeout_s: float,
               log=print) -> Dict:
    name = _leg_name(spec, cfg)
    # artifacts (and therefore the saved baselines _gate snapshots) are
    # NAMESPACED by platform (ISSUE 11): a cpu virtual-device baseline
    # must never gate a real-chip run — each backend regates its own
    plat = os.environ.get("JAXMC_MESHBENCH_PLATFORM", "cpu")
    metrics = os.path.join(
        out_dir,
        f"jaxmc_multichip_{plat}_{name}_d{devices}.json")
    # pre-ISSUE-11 baselines had no platform segment; those were all
    # measured on cpu virtual devices, so migrate them into the cpu
    # namespace instead of silently re-seeding the gate from current
    # performance (which would wave a regression through once)
    base = metrics.replace(".json", ".baseline.json")
    legacy = os.path.join(
        out_dir,
        f"jaxmc_multichip_{name}_d{devices}.baseline.json")
    if plat == "cpu" and not os.path.exists(base) \
            and os.path.exists(legacy):
        os.replace(legacy, base)
        log(f"meshbench: migrated pre-backend baseline -> "
            f"{os.path.basename(base)}")
    cmd = [sys.executable, "-m", "jaxmc.meshbench", "child",
           "--spec", spec, "--devices", str(devices),
           "--metrics-out", metrics]
    if cfg:
        cmd += ["--cfg", cfg]
    if exchange:
        cmd += ["--exchange", exchange]
    if timed:
        cmd += ["--timed"]
    if store_trace:
        cmd += ["--store-trace"]
    env = dict(os.environ, PYTHONPATH=_REPO)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, capture_output=True, text=True,
                           cwd=_REPO, env=env, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"ok": False, "leg": name, "devices": devices,
                "error": f"timed out after {timeout_s:.0f}s"}
    for line in (p.stdout or "").splitlines():
        if line.startswith(_RESULT_TAG):
            r = json.loads(line[len(_RESULT_TAG):])
            r["leg"] = name
            r["metrics_path"] = metrics
            r["child_wall_s"] = round(time.time() - t0, 3)
            return r
    tail = ((p.stderr or "") + (p.stdout or "")).strip() \
        .splitlines()[-3:] or ["no output"]
    return {"ok": False, "leg": name, "devices": devices,
            "error": f"child rc={p.returncode}: "
                     + " | ".join(t[:160] for t in tail)}


def _gate(metrics_path: str, log=print,
          ignore_phases: Tuple[str, ...] = ()) -> int:
    """Gate one leg's artifact against its saved baseline via
    `python -m jaxmc.obs diff --fail-on-regress` (first run snapshots
    the baseline, like make bench-check).  `ignore_phases` passes
    through to the diff (the backend-check leg excludes its cold-start
    compile walls — see jaxmc/backend/check.py)."""
    base = metrics_path.replace(".json", ".baseline.json")
    if not os.path.exists(metrics_path):
        return 0
    if not os.path.exists(base):
        import shutil
        shutil.copyfile(metrics_path, base)
        log(f"meshbench: baseline saved -> {base}")
        return 0
    from .obs.report import main as obs_main
    log(f"meshbench: gating {os.path.basename(metrics_path)} vs "
        f"saved baseline")
    argv = ["diff", "--fail-on-regress", "--threshold", "25"]
    if ignore_phases:
        argv += ["--ignore-phases", ",".join(ignore_phases)]
    return obs_main(argv + [base, metrics_path])


def cmd_check(args) -> int:
    failures = 0
    from .corpus import case_for_cfg
    for spec, cfg in args.rungs:
        skip = _needs_reference(spec, cfg)
        name = _leg_name(spec, cfg)
        if skip:
            print(f"MESHBENCH SKIP {name}: {skip}")
            continue
        cfgb = os.path.basename(
            cfg or os.path.splitext(spec)[0] + ".cfg")
        case = case_for_cfg(cfgb)
        for D in args.devices:
            # timed=True: the gated artifact measures the fully-warm
            # second run — one-shot cold walls are dominated by
            # compile/caps noise and would flap the 25% diff gate on a
            # loaded box
            r = _run_child(spec, cfg, D, args.exchange, True,
                           args.out_dir, store_trace=False,
                           timeout_s=args.timeout)
            if not r.get("ok"):
                print(f"MESHBENCH FAIL {name} D={D}: "
                      f"{r.get('error', r)}")
                failures += 1
                continue
            want = (case.generated, case.distinct) if case else None
            got = (r["generated"], r["distinct"])
            if want and want != got:
                print(f"MESHBENCH FAIL {name} D={D}: counts {got} != "
                      f"pinned {want}")
                failures += 1
                continue
            if r["host_syncs"] > r["levels"]:
                # one scalar-ring read per SUPERSTEP (ISSUE 10):
                # host_syncs may be well below the level count but can
                # never exceed it — more syncs than levels means row
                # traffic leaked into the level loop.  Validate BEFORE
                # the parseable ok-line: a leg must never print both
                # ok and FAIL
                print(f"MESHBENCH FAIL {name} D={D}: host_syncs "
                      f"{r['host_syncs']} > levels {r['levels']} "
                      f"(row traffic leaked into the level loop)")
                failures += 1
                continue
            print(f"MESHBENCH ok {name} D={D} exchange="
                  f"{r['exchange']}: "
                  f"{r['generated']} gen / "
                  f"{r['distinct']} distinct "
                  f"({r['states_per_sec']:,.0f} st/s, host_syncs="
                  f"{r['host_syncs']}, levels={r['levels']}, "
                  f"spill={r.get('a2a_spill', 0)})")
            if _gate(r["metrics_path"]):
                failures += 1
    print(f"meshbench check: {'FAIL' if failures else 'ok'} "
          f"({failures} failing legs)")
    return 1 if failures else 0


def cmd_bench(args) -> int:
    from . import obs
    rungs_out: List[Dict] = []
    failures = 0
    for spec, cfg in args.rungs:
        name = _leg_name(spec, cfg)
        skip = _needs_reference(spec, cfg)
        if skip:
            print(f"MESHBENCH SKIP {name}: {skip}")
            rungs_out.append({"rung": name, "spec": spec, "cfg": cfg,
                              "skipped": skip})
            continue
        curve: List[Dict] = []
        for D in args.devices:
            r = _run_child(spec, cfg, D, args.exchange, True,
                           args.out_dir, store_trace=False,
                           timeout_s=args.timeout)
            if not r.get("ok"):
                print(f"MESHBENCH FAIL {name} D={D}: "
                      f"{r.get('error', r)}")
                failures += 1
                curve.append({"devices": D,
                              "error": r.get("error", "failed")})
                continue
            point = {k: r[k] for k in
                     ("devices", "exchange", "generated",
                      "distinct", "wall_s", "warmup_wall_s",
                      "states_per_sec",
                      "states_per_sec_per_chip", "window_recompiles",
                      "host_syncs", "levels", "supersteps",
                      "superstep_levels", "exchange_bytes",
                      "exchange_bytes_per_level")
                     if k in r}
            for k in ("a2a_gamma", "a2a_spill", "a2a_max_bucket",
                      "shard_balance"):
                if k in r:
                    point[k] = r[k]
            curve.append(point)
            print(f"MESHBENCH point {name} D={D}: "
                  f"{r['states_per_sec']:,.0f} st/s "
                  f"({r['states_per_sec_per_chip']:,.0f} /chip), "
                  f"recompiles={r['window_recompiles']}, "
                  f"host_syncs={r['host_syncs']}/{r['levels']} lvls, "
                  f"xbytes/lvl={r['exchange_bytes_per_level']:,}, "
                  f"balance={r.get('shard_balance')}")
            if r["window_recompiles"] != 0:
                print(f"MESHBENCH FAIL {name} D={D}: warm run "
                      f"recompiled {r['window_recompiles']}x inside "
                      f"the window")
                failures += 1
            if r["host_syncs"] > r["levels"]:
                print(f"MESHBENCH FAIL {name} D={D}: host_syncs "
                      f"{r['host_syncs']} > levels {r['levels']}")
                failures += 1
            if _gate(r["metrics_path"]):
                failures += 1
        rungs_out.append({"rung": name, "spec": spec, "cfg": cfg,
                          "curve": curve})
    env = obs.environment_meta()
    art = {
        "schema": "jaxmc.multichip/1",
        "generated_at": time.time(),
        "mode": "mesh-resident",
        "platform": os.environ.get("JAXMC_MESHBENCH_PLATFORM", "cpu"),
        "virtual_devices":
            os.environ.get("JAXMC_MESHBENCH_PLATFORM", "cpu") == "cpu",
        "env": env,
        "devices_swept": list(args.devices),
        "rungs": rungs_out,
        "ok": failures == 0,
    }
    obs.write_json_atomic(args.out, art)
    try:  # ISSUE 17: land the per-chip curve in the run ledger too
        from .obs import ledger as _ledger
        _ledger.import_artifacts([args.out])
    except Exception:  # noqa: BLE001 — the ledger never breaks a gate
        pass
    print(f"meshbench: wrote {args.out} "
          f"({'FAIL' if failures else 'ok'}, {len(rungs_out)} rungs)")
    return 1 if failures else 0


def cmd_child(args) -> int:
    plat = os.environ.get("JAXMC_MESHBENCH_PLATFORM", "cpu")
    if plat == "cpu":
        # must precede ANY jax import in this process
        import re
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       "", os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = (
            flags.strip() +
            f" --xla_force_host_platform_device_count={args.devices}")
    import numpy as np
    import jax
    jax.config.update("jax_platforms", plat)
    from jax.sharding import Mesh
    from . import obs
    from .front.cfg import ModelConfig, parse_cfg
    from .sem.modules import Loader, bind_model
    from .corpus import case_for_cfg
    from .backend.mesh import MeshExplorer

    spec = os.path.join(_REPO, args.spec) \
        if not os.path.isabs(args.spec) else args.spec
    cfgp = args.cfg
    if cfgp is None:
        guess = os.path.splitext(spec)[0] + ".cfg"
        cfgp = guess if os.path.exists(guess) else None
    elif not os.path.isabs(cfgp):
        cfgp = os.path.join(_REPO, cfgp)
    if cfgp:
        with open(cfgp, encoding="utf-8") as fh:
            mc = parse_cfg(fh.read())
    else:
        mc = ModelConfig(specification="Spec")
    case = case_for_cfg(os.path.basename(cfgp)) if cfgp else None
    if case is not None and case.no_deadlock:
        mc.check_deadlock = False
    search = [os.path.dirname(spec)]
    if case is not None:
        search += case.include_dirs()
    model = bind_model(Loader(search).load_path(spec), mc)

    tel = obs.Telemetry(meta={"backend": "jax-mesh",
                              "devices": args.devices})
    from .compile.cache import enable_guarded_cache
    enable_guarded_cache(tel=tel)
    devs = jax.devices()
    if devs[0].platform != plat or len(devs) < args.devices:
        print(f"error: need {args.devices} {plat} devices, have "
              f"{len(devs)} {devs[0].platform}", file=sys.stderr)
        return 2
    mesh = Mesh(np.array(devs[:args.devices]), ("d",))
    obs.stamp_device(tel, devs)

    with obs.use(tel):
        mesh_caps = dict(case.mesh_caps) \
            if case is not None and case.mesh_caps else None
        me = MeshExplorer(model, mesh=mesh,
                          exchange=args.exchange or None,
                          store_trace=args.store_trace,
                          mesh_caps=mesh_caps)
        t0 = time.time()
        r = me.run()
        warm_wall = time.time() - t0
        result, wall = r, warm_wall
        window_recompiles = sum(1 for lv in tel.levels
                                if lv.get("fresh_compile"))
        lvl0, sync0, xb0 = (len(tel.levels),
                            tel.counters.get("mesh.host_syncs", 0),
                            tel.counters.get("mesh.exchange_bytes", 0))
        if args.timed:
            # the measured window: a fully-warm re-run on the same
            # engine (in-process jit cache + learned caps) — the
            # steady-state methodology of PR 5/6, per device count
            t0 = time.time()
            result = me.run()
            wall = time.time() - t0
            window_recompiles = sum(
                1 for lv in tel.levels[lvl0:] if lv.get("fresh_compile"))
    levels = len(tel.levels) - (lvl0 if args.timed else 0)
    host_syncs = tel.counters.get("mesh.host_syncs", 0) - \
        (sync0 if args.timed else 0)
    xbytes = tel.counters.get("mesh.exchange_bytes", 0) - \
        (xb0 if args.timed else 0)
    out = {
        "ok": bool(result.ok),
        "devices": args.devices,
        "exchange": me.exchange,
        "generated": int(result.generated),
        "distinct": int(result.distinct),
        "diameter": int(result.diameter),
        "truncated": bool(result.truncated),
        "wall_s": round(wall, 6),
        "warmup_wall_s": round(warm_wall, 6),
        "states_per_sec": round(result.generated / max(wall, 1e-9), 3),
        "states_per_sec_per_chip": round(
            result.generated / max(wall, 1e-9) / args.devices, 3),
        "window_recompiles": window_recompiles,
        "host_syncs": host_syncs,
        # host_syncs counts SUPERSTEPS (ISSUE 10): one scalar-ring
        # read per dispatch; `levels` stays the per-level record count
        "supersteps": host_syncs,
        "levels": levels,
        "exchange_bytes": int(xbytes),
        "exchange_bytes_per_level": int(xbytes / max(levels, 1)),
    }
    for k, src in (("superstep_levels", "mesh.superstep_levels"),
                   ("a2a_gamma", "mesh.a2a_gamma"),
                   ("a2a_spill", "mesh.a2a_spill"),
                   ("a2a_max_bucket", "mesh.a2a_max_bucket"),
                   ("shard_balance", "mesh.shard_balance")):
        if src in tel.gauges:
            out[k] = tel.gauges[src]
    if args.metrics_out:
        summary = tel.summary(result={
            "ok": bool(result.ok), "distinct": int(result.distinct),
            "generated": int(result.generated),
            "diameter": int(result.diameter),
            "truncated": bool(result.truncated),
            "wall_s": round(wall, 6)})
        summary["backend"] = "jax"
        summary["spec"] = args.spec
        summary["multichip"] = {k: out[k] for k in
                                ("devices", "exchange",
                                 "states_per_sec",
                                 "states_per_sec_per_chip",
                                 "window_recompiles", "host_syncs",
                                 "supersteps", "superstep_levels",
                                 "levels",
                                 "exchange_bytes_per_level")
                                if k in out}
        obs.write_json_atomic(args.metrics_out, summary)
        # ISSUE 17: every bench child lands its trajectory point in the
        # persistent run ledger (never raises, JAXMC_LEDGER=off disables)
        obs.append_summary(summary, source=args.metrics_out)
    print(_RESULT_TAG + json.dumps(out), flush=True)
    return 0


def _parse_rungs(vals: Optional[List[str]], default) -> List:
    if not vals:
        return list(default)
    out = []
    for v in vals:
        if "=" in v:
            s, c = v.split("=", 1)
            out.append((s, c))
        else:
            out.append((v, None))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m jaxmc.meshbench",
        description="multi-chip mesh parity + scaling harness")
    sub = ap.add_subparsers(dest="cmd", required=True)

    def common(p, dflt_devices):
        p.add_argument("--devices", default=dflt_devices,
                       help="comma-separated device counts")
        p.add_argument("--exchange", default=None,
                       choices=(None, "a2a", "gather"),
                       help="override the per-D default strategy")
        p.add_argument("--rung", action="append", default=None,
                       help="spec[=cfg], repeatable (repo-relative)")
        p.add_argument("--out-dir", default=os.environ.get(
            "JAXMC_PROBE_DIR", "/tmp"))
        p.add_argument("--timeout", type=float, default=float(
            os.environ.get("JAXMC_MESHBENCH_TIMEOUT", "900")))

    pc = sub.add_parser("check", help="parity legs (make multichip-check)")
    common(pc, "2,4")
    pb = sub.add_parser("bench", help="scaling curve (make multichip-bench)")
    common(pb, "1,2,4,8")
    pb.add_argument("--out", default=os.path.join(_REPO,
                                                  "MULTICHIP_r07.json"))
    pch = sub.add_parser("child")
    pch.add_argument("--spec", required=True)
    pch.add_argument("--cfg", default=None)
    pch.add_argument("--devices", type=int, required=True)
    pch.add_argument("--exchange", default=None)
    pch.add_argument("--timed", action="store_true")
    pch.add_argument("--store-trace", action="store_true")
    pch.add_argument("--metrics-out", default=None)
    args = ap.parse_args(argv)

    if args.cmd == "child":
        return cmd_child(args)
    args.devices = [int(x) for x in str(args.devices).split(",") if x]
    args.rungs = _parse_rungs(
        args.rung, CHECK_RUNGS if args.cmd == "check" else BENCH_RUNGS)
    return cmd_check(args) if args.cmd == "check" else cmd_bench(args)


if __name__ == "__main__":
    sys.exit(main())
