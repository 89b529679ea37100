r"""Kernel-vs-interpreter bench leg (ISSUE 6): `python -m jaxmc.kernelbench`.

A CPU TEST GATE, not a chip path: with no JAXMC_PLATFORM/JAX_PLATFORMS
it pins jax to "cpu" (main()), and what it times is XLA:CPU — never a
device metric.  The chip's proof of life is chip_smoke.py.

The compiled path must not lose to the exact interpreter it replaces.
This driver makes that a GATE: for one spec it measures, on the same
workload,

  interp  the serial exact interpreter (engine/explore.py), fresh
          Explorer per repeat, min-of-repeats wall;
  kernel  the cpu-XLA/device engine (tpu/bfs.py), built once; the FIRST
          run is the untimed warm-up (XLA compile + capacity training +
          capacity-profile persist), then min-of-repeats over fully-warm
          re-runs — the steady-state methodology PR 5 established for
          the raft bench, applied per corpus rung.

Counts must be BIT-IDENTICAL between the two engines (the packed
encoding must not change what is counted), and two metrics artifacts
(schema jaxmc.metrics/2) are written so the gate runs through the same
`python -m jaxmc.obs diff --fail-on-regress` machinery as every other
bench-check leg: artifacts are ordered [interp, kernel], so a kernel
slower than the interpreter raises the REGRESS states/sec flag and
fails the leg.

Used by `make bench-check` over the repo-local rungs (transfer_scaled,
viewtoy, symtoy — no reference corpus needed).
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_model(spec: str, cfg: Optional[str], includes):
    from .front.cfg import parse_cfg, ModelConfig
    from .sem.modules import Loader, bind_model
    if cfg is None:
        guess = os.path.splitext(spec)[0] + ".cfg"
        cfg = guess if os.path.exists(guess) else None
    if cfg:
        with open(cfg, encoding="utf-8") as fh:
            mc = parse_cfg(fh.read())
    else:
        mc = ModelConfig(specification="Spec")
    from .corpus import case_for_cfg
    pin = case_for_cfg(os.path.basename(cfg)) if cfg else None
    if pin is not None and pin.no_deadlock:
        mc.check_deadlock = False
    ldr = Loader([os.path.dirname(os.path.abspath(spec))] +
                 list(includes))
    return bind_model(ldr.load_path(spec), mc), pin


def _artifact(path: str, backend: str, spec: str, platform: str,
              wall_s: float, result, repeats: int, note: str) -> None:
    from . import obs
    env = obs.environment_meta()
    env["platform"] = platform
    art = {
        "schema": "jaxmc.metrics/2",
        "started_at": time.time(),
        "wall_s": round(wall_s, 6),
        "backend": backend,
        "spec": spec,
        "phases": [{"name": "search", "wall_s": round(wall_s, 6),
                    "count": repeats}],
        "counters": {},
        "gauges": {"kernelbench.note": note},
        "levels": [],
        "env": env,
        "result": {"ok": bool(result.ok),
                   "distinct": int(result.distinct),
                   "generated": int(result.generated),
                   "diameter": int(result.diameter),
                   "truncated": bool(result.truncated),
                   "wall_s": round(wall_s, 6)},
    }
    obs.write_json_atomic(path, art)
    # ISSUE 17: each gate leg lands a trajectory point in the run ledger
    obs.append_summary(art, source=path)


def run_leg(spec: str, cfg: Optional[str], out_dir: str,
            repeats: int = 2, interp_repeats: int = 1,
            engine: str = "resident", includes=(), log=print) -> int:
    """Measure both engines, write the two artifacts, run the gate.
    Returns the gate's exit status (0 ok, 1 kernel lost)."""
    from .engine.explore import Explorer
    from .backend.bfs import TpuExplorer

    name = os.path.splitext(os.path.basename(spec))[0]

    # ---- serial interpreter: fresh engine per repeat, min wall ----
    iwalls, iref = [], None
    for _ in range(max(interp_repeats, 1)):
        model, pin = _load_model(spec, cfg, includes)
        r = Explorer(model).run()
        iwalls.append(r.wall_s)
        if iref is None:
            iref = r
        assert (r.generated, r.distinct) == (iref.generated,
                                             iref.distinct), \
            "interpreter repeats disagree (nondeterminism?)"
    interp_wall = min(iwalls)
    interp_rate = iref.generated / max(interp_wall, 1e-9)

    # ---- kernel: one engine; warm-up run (compile + caps + profile),
    # then min-of-repeats over fully warm re-runs ----
    model, pin = _load_model(spec, cfg, includes)
    kw = dict(store_trace=False)
    if engine == "resident":
        # the manifest's committed res_caps record sizes the capacity
        # buckets (small model -> small sorts); the gate measurement
        # itself stays profile-independent so it is reproducible from
        # the repo alone
        kw["resident"] = True
        kw["cap_profile"] = False
        rc = dict(pin.res_caps) if pin is not None and pin.res_caps \
            else None
        if rc:
            kw["chunk"] = int(rc.pop("chunk", 2048))
            kw["res_caps"] = rc
    ex = TpuExplorer(model, **kw)
    t0 = time.time()
    rw = ex.run()  # warm-up: XLA compile + capacity training, untimed
    warm_wall = time.time() - t0
    kwalls = []
    for _ in range(repeats):
        t0 = time.time()
        rk = ex.run()
        kwalls.append(time.time() - t0)
        assert (rk.generated, rk.distinct, rk.ok) == \
            (rw.generated, rw.distinct, rw.ok), "kernel repeats disagree"
    kernel_wall = min(kwalls)
    kernel_rate = rk.generated / max(kernel_wall, 1e-9)

    # ---- exactness gate: the packed kernel must COUNT identically ----
    assert (rk.generated, rk.distinct, rk.ok) == \
        (iref.generated, iref.distinct, iref.ok), \
        (f"{name}: kernel counts diverge from the interpreter: "
         f"kernel {rk.generated}/{rk.distinct}/ok={rk.ok} vs interp "
         f"{iref.generated}/{iref.distinct}/ok={iref.ok}")

    import jax
    platform = jax.devices()[0].platform
    os.makedirs(out_dir, exist_ok=True)
    a_interp = os.path.join(out_dir, f"jaxmc_kernelbench_{name}_interp.json")
    a_kernel = os.path.join(out_dir, f"jaxmc_kernelbench_{name}_kernel.json")
    _artifact(a_interp, "interp", spec, "interp", interp_wall, iref,
              max(interp_repeats, 1),
              f"serial exact interpreter, min of {max(interp_repeats, 1)}")
    _artifact(a_kernel, "jax", spec, platform, kernel_wall, rk, repeats,
              f"{engine} engine on {platform}, min of {repeats} after "
              f"one warm-up ({warm_wall:.2f}s compile+ramp excluded); "
              f"W={ex.W} PW={ex.PW} packed"
              f"={'no' if ex.plan.identity else 'yes'}")
    log(f"kernelbench {name}: interp {interp_rate:,.0f} st/s "
        f"({iref.generated} gen / {interp_wall:.4f}s) | kernel[{engine}/"
        f"{platform}] {kernel_rate:,.0f} st/s ({kernel_wall:.4f}s, "
        f"warm-up {warm_wall:.2f}s excluded) | "
        f"ratio {kernel_rate / max(interp_rate, 1e-9):.2f}x | "
        f"W={ex.W} PW={ex.PW}")

    # ---- the gate: same machinery as every bench-check leg ----
    from .obs.report import main as obs_main
    return obs_main(["diff", "--fail-on-regress", "--threshold", "0",
                     a_interp, a_kernel])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m jaxmc.kernelbench",
        description="kernel-vs-interpreter states/sec gate for one spec")
    ap.add_argument("spec")
    ap.add_argument("--cfg", default=None)
    ap.add_argument("-I", "--include", action="append", default=[])
    ap.add_argument("--out-dir", default="/tmp",
                    help="where the two metrics artifacts land")
    ap.add_argument("--repeats", type=int, default=2,
                    help="timed kernel re-runs (min wall wins)")
    ap.add_argument("--interp-repeats", type=int, default=1,
                    help="interpreter repeats (the expensive side: one "
                         "full exact search each)")
    ap.add_argument("--engine", choices=("resident", "level"),
                    default="resident")
    args = ap.parse_args(argv)
    try:
        import jax
        jax.config.update("jax_platforms",
                          os.environ.get("JAXMC_PLATFORM") or
                          os.environ.get("JAX_PLATFORMS") or "cpu")
    except ImportError:
        print("error: the jax backend is unavailable in this build",
              file=sys.stderr)
        return 2
    return run_leg(args.spec, args.cfg, args.out_dir,
                   repeats=args.repeats,
                   interp_repeats=args.interp_repeats,
                   engine=args.engine, includes=args.include)


if __name__ == "__main__":
    sys.exit(main())
