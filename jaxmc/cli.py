r"""jaxmc command-line interface.

    python -m jaxmc check SPEC.tla [--cfg F.cfg]
        [--backend interp|jax|auto|cpu|gpu|tpu]
    python -m jaxmc simulate SPEC.tla [--walks N --depth N --coverage]
    python -m jaxmc info SPEC.tla
    python -m jaxmc.serve ...       (checking-as-a-service daemon)

Mirrors the reference's `make test` contract (tlc *tla, Makefile:6-7): check a
spec against its model config, print TLC-style progress and a counterexample
trace on violation. Exit status 0 = no error, 1 = violation, 2 = usage/error,
143 = drained on SIGTERM (checkpointed, resumable).

Since ISSUE 7 the check flow itself lives in jaxmc/session.py
(CheckSession: parse -> compile -> explore as resumable stages); this
module is the thin driver that owns argument parsing, output rendering,
and the exit-code policy — stdout/stderr and exit codes are
byte-identical to the pre-session CLI.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def cmd_check(args) -> int:
    from . import drain, obs

    t0 = time.time()
    # telemetry is a PARALLEL channel: stdout stays byte-identical; a
    # NullTelemetry (every method a no-op) serves runs that asked for no
    # artifact, so the engines' instrumentation costs nothing
    want_tel = bool(args.metrics_out or args.trace or args.profile)
    tel = obs.Telemetry(
        trace_path=args.trace,
        meta={"command": "check", "backend": args.backend,
              "spec": args.spec, "cfg": args.cfg,
              "argv": list(sys.argv[1:]),
              "env": obs.environment_meta()}) if want_tel \
        else obs.NullTelemetry()
    if args.profile:
        # per-dispatch device profiling (ISSUE 17, obs/prof.py): wall
        # mode adds block-until-ready walls + byte accounting to the
        # always-on dispatch counters; a sync cannot change values, so
        # counts/traces stay bit-identical to a profile-off run.  xla
        # mode stays cheap (no forced sync): the trace is the run a
        # user has
        tel.prof.mode = args.profile
    log = obs.Logger(tel, quiet=args.quiet)
    # the watchdog names a wedged phase (device init, a pathological BFS
    # level) on stderr and in the trace WHILE it hangs — start() is a
    # no-op on the NullTelemetry, so runs without an artifact pay nothing
    wd = obs.Watchdog(tel).start()
    # graceful shutdown (ISSUE 7 satellite): SIGTERM requests a
    # cooperative drain — the engine checkpoints at its next safe
    # boundary and returns, so the finally below closes spans and joins
    # the watchdog instead of leaking both; the process exits 143 with
    # the reason named (jaxmc/drain.py)
    drain.install()
    xla_tracing = args.profile == "xla" and _start_xla_trace(args, tel)
    try:
        with obs.use(tel):
            return _run_check(args, tel, log, t0)
    finally:
        if xla_tracing:
            _stop_xla_trace()
        wd.stop()
        tel.close()


def _start_xla_trace(args, tel) -> bool:
    """--profile=xla: wrap the whole run in a jax.profiler trace
    capture to a named artifact dir (JAXMC_XLA_TRACE_DIR, else next to
    --metrics-out, else a fresh tempdir), recorded as the benchmark
    records (bench/drivers/recheck.py): no Python tracer, host events
    at level 2, so the program's `jaxmc.<span>` annotations lie beside
    the runtime's own events and the device line.  Best-effort: a
    backend without profiler support degrades to wall-mode profiling
    with a warning, never a failed run."""
    tdir = os.environ.get("JAXMC_XLA_TRACE_DIR") or \
        (args.metrics_out + ".xla" if args.metrics_out else None)
    if tdir is None:
        import tempfile
        tdir = tempfile.mkdtemp(prefix="jaxmc-xla-")
    try:
        import jax
        opt = jax.profiler.ProfileOptions()
        opt.python_tracer_level = 0
        opt.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opt)
    except Exception as e:  # noqa: BLE001 — profiling is best-effort
        print(f"warning: --profile=xla trace capture unavailable "
              f"({e}); continuing with wall-mode profiling",
              file=sys.stderr)
        tel.prof.mode = tel.prof.WALL
        return False
    tel.prof.xla_trace_dir = tdir
    print(f"-- profile: xla trace capture -> {tdir}", file=sys.stderr)
    return True


def _stop_xla_trace() -> None:
    try:
        import jax
        jax.profiler.stop_trace()
    except Exception:  # noqa: BLE001 — never mask the run's own exit
        pass


def _metrics_error(args, tel, error: str) -> None:
    if args.metrics_out:
        tel.write_metrics(args.metrics_out,
                          result={"ok": False, "distinct": 0,
                                  "generated": 0, "diameter": 0,
                                  "truncated": False, "error": error})


def _run_check(args, tel, log, t0) -> int:
    from .engine.explore import format_trace
    from .session import CheckSession, SessionConfig

    if args.analyze not in ("off", "warn", "strict"):
        # argparse validates only user-typed values against choices —
        # a typo'd JAXMC_ANALYZE env default must fail LOUDLY, not
        # silently degrade a strict CI gate to warn
        print(f"error: invalid --analyze/JAXMC_ANALYZE value "
              f"{args.analyze!r} (expected off, warn or strict)",
              file=sys.stderr)
        _metrics_error(args, tel, f"invalid analyze mode {args.analyze!r}")
        return 2
    sess = CheckSession(SessionConfig.from_args(args), tel=tel, log=log)
    if args.analyze != "off":
        # static analysis stage (ISSUE 9), BEFORE parse so a cfg defect
        # that would make bind_model refuse still reports its full
        # diagnostic list; strict mode refuses to go further
        from .session import AnalyzeError
        try:
            for d in sess.analyze():
                print(f"analyze: {d.render()}", file=sys.stderr)
        except AnalyzeError as ex:
            for d in ex.diagnostics:
                print(f"analyze: {d.render()}", file=sys.stderr)
            print(f"error: --analyze=strict refused the run ({ex}); "
                  f"fix the spec/cfg or re-run with --analyze=warn",
                  file=sys.stderr)
            _metrics_error(args, tel, f"analyze strict: {ex}")
            return 2
    if sess.parse() == "assumes":
        rc = sess.run_assumes()
        if args.metrics_out:
            tel.write_metrics(args.metrics_out,
                              result={"ok": rc == 0, "distinct": 0,
                                      "generated": 0, "diameter": 0,
                                      "truncated": False,
                                      "mode": "assumes"})
        return rc
    if args.backend == "interp":
        res = sess.explore()
    else:
        from . import faults
        from .compile.vspec import CompileError, ModeError
        from .engine.ckpt import CkptError
        faults.ensure_shared_state()  # one budget for run + fallback
        try:
            sess.compile()
            res = sess.explore()
        except ImportError as e:
            print(f"error: the jax backend is not available in this build "
                  f"({e})", file=sys.stderr)
            _metrics_error(args, tel, f"jax unavailable: {e}")
            return 2
        except ModeError as e:
            print(f"error: {e}", file=sys.stderr)
            _metrics_error(args, tel, str(e))
            return 2
        except CompileError as e:
            print(f"error: this spec is outside the jax backend's "
                  f"compilable subset ({e}); re-run with "
                  f"--backend interp", file=sys.stderr)
            _metrics_error(args, tel, str(e))
            return 2
        except CkptError:
            raise  # main() maps checkpoint defects to exit 2
        except (faults.FaultInjected, RuntimeError, OSError, MemoryError,
                ConnectionError) as e:
            # TERMINAL device failure: when the search left a host
            # snapshot, demote_to_cpu resumes it on the parallel CPU
            # engine instead of losing hours of progress; with nothing
            # to resume (device init, engine build, the first compile)
            # it re-raises and main() exits 2 naming the fault.
            # Spec-compatibility refusals (ModeError/CompileError) and
            # semantic errors (EvalError) are handled above/elsewhere —
            # the interp would hit those identically, so no fallback.
            if args.no_device_fallback:
                raise
            res = sess.demote_to_cpu(e)
    wall = time.time() - t0
    print(f"{res.generated} states generated, {res.distinct} distinct states "
          f"found ({res.generated / max(res.wall_s, 1e-9):.0f} states/sec, "
          f"backend={sess.finished_on}, wall {wall:.2f}s)")
    for w in getattr(res, "warnings", []):
        print(f"Warning: {w}")
    if args.metrics_out:
        mst = getattr(sess.model, "_memo", None)
        if mst is not None:
            tel.gauge("memo.hits", mst.hits)
            tel.gauge("memo.misses", mst.misses)
        result = {"ok": res.ok, "distinct": res.distinct,
                  "generated": res.generated, "diameter": res.diameter,
                  "truncated": bool(getattr(res, "truncated", False)),
                  "wall_s": round(res.wall_s, 6),
                  "finished_on": sess.finished_on,
                  "warnings": list(getattr(res, "warnings", []))}
        if getattr(res, "drained", False):
            result["drained"] = True
        # ISSUE 12 result surface: seen-key mode, the fingerprint
        # collision bound, the named exhausted resource on truncation,
        # and the tier-hierarchy summary when the run spilled
        result["seen_mode"] = getattr(res, "seen_mode", "exact")
        if getattr(res, "collision_p", None) is not None:
            result["collision_p"] = res.collision_p
        if getattr(res, "trunc_reason", None):
            result["trunc_reason"] = res.trunc_reason
        if getattr(res, "tiers", None):
            result["tiers"] = res.tiers
        if res.violation is not None:
            result["violation"] = {"kind": res.violation.kind,
                                   "name": res.violation.name}
        tel.write_metrics(args.metrics_out, result=result)
    if res.ok:
        if getattr(res, "drained", False):
            # cooperative SIGTERM drain: checkpointed at a safe
            # boundary, spans closed, resumable — exit 143, never a
            # silent 0 (the search did NOT complete)
            from . import drain
            print("Search DRAINED at a safe boundary - no error found "
                  "in the explored prefix.")
            print(f"jaxmc: drained ({drain.reason()})"
                  + (f"; resume with --resume {args.checkpoint}"
                     if args.checkpoint else "; no checkpoint was "
                     "configured"), file=sys.stderr)
            return drain.DRAIN_EXIT_CODE
        if getattr(res, "truncated", False):
            print("Search TRUNCATED at state limit - no error found in the "
                  "explored prefix.")
        else:
            print("Model checking completed. No error has been found.")
        return 0
    print(format_trace(res.violation))
    return 1


def cmd_simulate(args) -> int:
    """TLC's -simulate mode: random behaviors, invariants checked along
    the way (engine/simulate.py)."""
    from .engine.simulate import random_walks
    from .engine.explore import format_trace
    from .session import load_model

    model = load_model(args.spec, args.cfg, no_deadlock=args.no_deadlock,
                       includes=args.include)
    v = random_walks(model, n_walks=args.walks, depth=args.depth,
                     seed=args.seed, check_invariants=True,
                     coverage_guided=args.coverage,
                     check_deadlock=model.check_deadlock)
    if v is None:
        print(f"{args.walks} behaviors of length <= {args.depth} simulated. "
              f"No error has been found.")
        return 0
    print(format_trace(v))
    return 1


def cmd_sweep(args) -> int:
    from .corpus import sweep
    return 1 if sweep(backend=args.backend, include_slow=args.slow,
                      metrics_out=args.metrics_out) else 0


def cmd_info(args) -> int:
    from .sem.modules import Loader
    from .front import tla_ast as A

    ldr = Loader([os.path.dirname(os.path.abspath(args.spec))])
    mod = ldr.load_path(args.spec)
    print(f"module {mod.name}")
    print(f"  extends:   {', '.join(mod.ast.extends) or '-'}")
    print(f"  constants: {', '.join(n for n, _ in mod.constants) or '-'}")
    print(f"  variables: {', '.join(mod.variables) or '-'}")
    ops = [u.name for u in mod.ast.units if isinstance(u, A.OpDef)]
    print(f"  operators: {len(ops)}")
    # batch compatibility surface (ISSUE 13): which constants would
    # ride the batch axis, the layout-compat class key, and analyze's
    # state-space estimate — the parse-time facts the serve fleet
    # schedules on.  Needs a bindable cfg; silent otherwise (info on a
    # bare module stays cfg-free).
    cfgp = getattr(args, "cfg", None) or \
        os.path.splitext(args.spec)[0] + ".cfg"
    if os.path.exists(cfgp):
        # ONE model load + ONE bounds fixpoint serve both the batch
        # line and the analysis surface below
        model = rep = None
        try:
            from .session import load_model
            from .analyze.bounds import infer_state_bounds
            model = load_model(args.spec, cfgp, False)
            rep = infer_state_bounds(model)
            model._bounds_report = rep
        except Exception:  # noqa: BLE001 — info must never fail on
            model = None   # an analysis defect
        try:
            from .session import SessionConfig, batch_profile
            prof = batch_profile(SessionConfig(
                spec=args.spec, cfg=cfgp, backend="jax",
                host_seen=True), model=model)
        except Exception:  # noqa: BLE001
            prof = None
        if prof is not None:
            est = prof.cost_estimate \
                if prof.cost_estimate is not None else "?"
            print(f"  batch:     sig={prof.bsig} "
                  f"lifted=[{', '.join(prof.lift) or '-'}] "
                  f"est_states={est}")
        if model is None:
            print("  analyze:   unavailable (model does not bind)")
            return 0
        # analysis surface (ISSUE 15): why a spec did or did not get
        # the fast path — proven per-element lane bounds, the
        # predicted state count the capacity ladder/fast lane reads,
        # and the arm-independence matrix regrouping/--por consume
        try:
            from .analyze.bounds import state_space_estimate
            from .analyze.independence import (independence_report,
                                               por_refusal)
            if rep is None:
                print("  bounds:    analysis bailed (no proofs)")
            else:
                ebs = rep.element_bounds()
                lanes = rep.lane_bounds()
                parts = []
                for v in model.vars:
                    if v in lanes:
                        parts.append(f"{v}∈[{lanes[v][0]},"
                                     f"{lanes[v][1]}]")
                    elif v in ebs:
                        parts.append(f"{v}:{ebs[v]!r}")
                est = state_space_estimate(model, rep)
                print(f"  bounds:    "
                      f"{'converged' if rep.converged else 'TRUNCATED'}"
                      f" proven=[{', '.join(parts) or '-'}] "
                      f"predicted_states="
                      f"{est if est is not None else '?'}")
            irep = independence_report(model)
            refusal = por_refusal(model)
            print(f"  independence: {len(irep.labels)} arms, "
                  f"{irep.commuting_pairs()} commuting pairs, "
                  f"{len(irep.por_safe)} por-safe"
                  + (f" (--por disabled: {refusal})" if refusal
                     else ""))
            for row in irep.matrix_rows():
                print(f"    {row}")
            # dynamic-key classification (ISSUE 18): WHY each arm is
            # (or is not) element-commuting — the key expressions the
            # element-atom footprints resolved to
            print("  key classes:")
            for row in irep.keyclass_rows():
                print(f"    {row}")
        except Exception as ex:  # noqa: BLE001 — info must never fail
            if os.environ.get("JAXMC_DEBUG"):
                raise
            print(f"  analyze:   unavailable ({type(ex).__name__})")
    return 0


def main(argv=None) -> int:
    from .compile.vspec import Bounds  # no jax dependency
    from .backend import BACKEND_CHOICES  # no jax dependency
    ap = argparse.ArgumentParser(prog="jaxmc")
    sub = ap.add_subparsers(dest="cmd", required=True)

    c = sub.add_parser("check", help="model-check a spec")
    c.add_argument("spec")
    c.add_argument("--cfg", default=None,
                   help="the model's .cfg (default: <spec>.cfg). A "
                        "SYMMETRY line reduces on every backend; the "
                        "device engines canonicalise rows by SORTING "
                        "the per-member sub-vectors where the group is "
                        "a product of full symmetric groups "
                        "(Permutations(S)) whose members the state only "
                        "indexes by — any |S| — and otherwise by "
                        "unrolling one transform per group element, up "
                        "to JAXMC_SYM_GROUP_LIMIT (64) of them; above "
                        "that the device search runs UNREDUCED with a "
                        "warning (gauge symmetry.form says which)")
    c.add_argument("-I", "--include", action="append", default=[],
                   help="extra module search directories (MC shims "
                        "extending reference specs)")
    c.add_argument("--backend", choices=list(BACKEND_CHOICES),
                   default="interp",
                   help="interp = the exact Python engine; jax = the "
                        "XLA engine on whatever platform jax picks "
                        "(honors --platform); cpu|gpu|tpu = the XLA "
                        "engine PINNED to that platform; auto = probe "
                        "the visible platforms with the preflight "
                        "oracle (seconds, hang-proof) and run on the "
                        "best live one (verdict in the metrics "
                        "artifact as backend.oracle_choice)")
    c.add_argument("--platform", default=os.environ.get("JAXMC_PLATFORM"),
                   help="pin the jax platform (e.g. 'cpu', 'tpu') inside "
                        "this process before device init, for --backend "
                        "jax (env: JAXMC_PLATFORM)")
    c.add_argument("--max-states", type=int, default=None)
    c.add_argument("--workers", type=int, metavar="N", default=None,
                   help="interp backend: worker processes for parallel "
                        "frontier expansion (default: JAXMC_WORKERS, "
                        "else min(cpu_count, 8); 1 = the serial engine; "
                        "results are bit-identical either way)")
    c.add_argument("--no-deadlock", action="store_true",
                   help="disable deadlock checking")
    c.add_argument("--analyze", choices=["off", "warn", "strict"],
                   default=os.environ.get("JAXMC_ANALYZE", "off"),
                   help="static analysis stage between parse and "
                        "compile (ISSUE 9): lint the spec/cfg pair "
                        "(unused defs/VARIABLEs/CONSTANTs, dead "
                        "actions, cfg mismatches, symmetry hazards — "
                        "stable JMC* codes). warn prints diagnostics "
                        "on stderr and continues; strict exits 2 on "
                        "any error diagnostic BEFORE compiling "
                        "(env: JAXMC_ANALYZE). Bounds inference and "
                        "demotion prediction are independent of this "
                        "flag (JAXMC_ANALYZE_BOUNDS / "
                        "JAXMC_ANALYZE_PREDICT, both default on)")
    c.add_argument("--por", action="store_true",
                   help="partial-order reduction (ISSUE 15, opt-in): "
                        "expand ONE provably-commuting invisible arm "
                        "per state (persistent-set filter; BFS cycle "
                        "proviso) instead of every enabled arm. "
                        "Preserves invariant/deadlock verdicts and "
                        "reports traces that replay under unreduced "
                        "semantics — raw state counts SHRINK by "
                        "design. Runs on the exact interpreter engine; "
                        "disabled with a named reason on CONSTRAINT/"
                        "SYMMETRY/VIEW/temporal models. Reduction "
                        "facts: jaxmc info --cfg prints the arm "
                        "independence matrix")
    c.add_argument("--no-device-fallback", action="store_true",
                   help="jax backend: exit on a terminal device failure "
                        "even when a host snapshot exists (by default a "
                        "run that fails mid-search with --checkpoint set "
                        "resumes its last host snapshot on the parallel "
                        "CPU engine; a failure with nothing to resume "
                        "always exits 2)")
    c.add_argument("--quiet", action="store_true")
    c.add_argument("--progress-every", type=float, default=30.0)
    c.add_argument("--seq-cap", type=int, default=Bounds.seq_cap,
                   help="jax backend: sequence-length capacity FLOOR "
                        "(actual cap = max(floor, observed * margin); "
                        "raise if a run aborts with capacity overflow)")
    c.add_argument("--grow-cap", type=int, default=Bounds.grow_cap,
                   help="jax backend: growing-set capacity floor")
    c.add_argument("--kv-cap", type=int, default=Bounds.kv_cap,
                   help="jax backend: message-table domain capacity floor")
    c.add_argument("--no-trace", action="store_true",
                   help="jax backend: skip trace bookkeeping (benchmarks)")
    c.add_argument("--host-seen", action="store_true",
                   help="jax backend: keep the seen-set in the native C++ "
                        "fingerprint store (state spaces beyond device "
                        "memory; usually faster)")
    c.add_argument("--seen", choices=("auto", "exact", "fingerprint"),
                   default="auto",
                   help="jax backend: dedup-key mode. auto = exact keys "
                        "on narrow layouts, 128-bit fingerprints past "
                        "FP_THRESHOLD (today's default); fingerprint = "
                        "force fingerprints on ANY layout (4-8x the "
                        "states per seen tier; the collision-"
                        "probability bound is reported in the result); "
                        "exact = refuse to fingerprint (errors on wide "
                        "layouts / resident / host-seen)")
    c.add_argument("--seen-cap", type=int, default=None, metavar="ROWS",
                   help="jax backend: device seen-table cap in key "
                        "rows (env: JAXMC_SEEN_CAP). On overflow the "
                        "sorted device prefix SPILLS to host-RAM and "
                        "then disk tiers (out-of-core checking) "
                        "instead of growing device memory — counts and "
                        "traces stay bit-identical to the uncapped "
                        "run. The cap must seat the widest level's "
                        "candidates beside the hot keys; one that "
                        "cannot is grown past, named by the gauge "
                        "tier.cap_breached and result.tiers. Cold "
                        "tiers last one search: a re-check on a warm "
                        "session spills and probes again. Default: no "
                        "cap (grow on device)")
    c.add_argument("--seen-spill", default=None, metavar="DIR",
                   help="jax backend: disk-tier directory for spilled "
                        "seen-set runs (env: JAXMC_SPILL_DIR; default "
                        "a temp dir). Host-RAM tier budget: "
                        "JAXMC_TIER_HOST_KEYS keys")
    c.add_argument("--sample", type=int, nargs=3,
                   default=[800, 40, 60],
                   metavar=("BFS", "WALKS", "DEPTH"),
                   help="jax backend: layout-sampling effort (BFS-prefix "
                        "states, random walks, walk depth). Deep models "
                        "need more walks/depth so every container shape "
                        "and record variant is OBSERVED - an unobserved "
                        "variant demotes its reader kernels to the "
                        "interpreter (hybrid) or aborts")
    c.add_argument("--chunk", type=int, default=2048,
                   help="jax backend: frontier rows expanded per kernel "
                        "call (bounds device memory; host-seen mode)")
    c.add_argument("--resident", action="store_true",
                   help="jax backend: run the WHOLE search device-side "
                        "(frontier, fingerprint set, level loop in one "
                        "jitted while_loop, zero host syncs per level); "
                        "a violation's counterexample is walked back "
                        "over a state log kept on the device unless "
                        "--no-trace (which saves the log's memory); no "
                        "temporal properties")
    c.add_argument("--devices", type=int, default=None, metavar="N",
                   help="device backends: shard the frontier and the "
                        "seen set over the first N devices of the "
                        "platform (the mesh engine: owner-hashed "
                        "128-bit fingerprints, all_to_all exchange per "
                        "level, counts identical to one device); exits "
                        "2 when fewer than N are visible")
    c.add_argument("--checkpoint", default=None,
                   help="write periodic checkpoints to this file "
                        "(TLC's states/ equivalent; both backends)")
    c.add_argument("--checkpoint-every", type=float, default=600.0)
    c.add_argument("--resume", default=None,
                   help="resume a run from a checkpoint (the backend and "
                        "device mode must match the writing run's)")
    c.add_argument("--metrics-out", default=None, metavar="FILE",
                   help="write an end-of-run JSON metrics artifact: "
                        "phase wall times, per-level BFS counts, "
                        "expansion-mode/memo/fingerprint/compile-cost "
                        "counters, the env fingerprint and the result "
                        "block (schema jaxmc.metrics/4; see "
                        "jaxmc/obs/schema.py; render/compare with "
                        "python -m jaxmc.obs report|diff|top)")
    c.add_argument("--trace", default=None, metavar="FILE",
                   help="stream telemetry events as JSONL while the run "
                        "is live (span_open/span/level/log plus "
                        "watchdog heartbeat/stall beats); a killed "
                        "run leaves open spans naming the phase it "
                        "died in, and a wedged phase is flagged by a "
                        "stall event while it hangs (knobs: "
                        "JAXMC_HEARTBEAT_EVERY/JAXMC_STALL_FACTOR/"
                        "JAXMC_STALL_MIN_S)")
    c.add_argument("--profile", nargs="?", const="wall", default=None,
                   choices=("wall", "xla"),
                   help="per-dispatch device profiling (obs/prof.py): "
                        "block-until-ready wall, bytes and recompiles "
                        "per named dispatch site, stamped into "
                        "--metrics-out as the prof{} block (render "
                        "with python -m jaxmc.obs top). --profile=xla "
                        "instead captures a jax.profiler trace of the "
                        "run as it is (no forced sync, no Python "
                        "tracer) to JAXMC_XLA_TRACE_DIR (default: "
                        "METRICS_OUT.xla/), with the program's spans "
                        "and kernel scopes in it. Profiling never "
                        "changes counts or traces")
    c.set_defaults(fn=cmd_check)

    m = sub.add_parser("simulate",
                       help="check invariants along random behaviors "
                            "(TLC -simulate)")
    m.add_argument("spec")
    m.add_argument("--cfg", default=None)
    m.add_argument("-I", "--include", action="append", default=[])
    m.add_argument("--walks", type=int, default=100)
    m.add_argument("--depth", type=int, default=100)
    m.add_argument("--seed", type=int, default=0)
    m.add_argument("--coverage", action="store_true",
                   help="bias toward rarely-taken action families")
    m.add_argument("--no-deadlock", action="store_true",
                   help="disable deadlock reporting")
    m.set_defaults(fn=cmd_simulate)

    i = sub.add_parser("info", help="parse a spec and print a summary")
    i.add_argument("spec")
    i.add_argument("--cfg", default=None,
                   help="model config for the batch-compat surface "
                        "(default: <spec>.cfg when present)")
    i.set_defaults(fn=cmd_info)

    s = sub.add_parser("sweep",
                       help="check the WHOLE corpus with expected "
                            "verdicts (the reference's `tlc *tla`)")
    s.add_argument("--backend", choices=("interp", "jax"),
                   default="interp")
    s.add_argument("--slow", action="store_true",
                   help="include the multi-minute models")
    s.add_argument("--metrics-out", default=None, metavar="FILE",
                   help="write a per-case JSON metrics artifact "
                        "(status, wall time, expansion mode) next to "
                        "the sweep log")
    s.set_defaults(fn=cmd_sweep)

    args = ap.parse_args(argv)
    from .engine.ckpt import CkptError  # no jax dependency
    try:
        return args.fn(args)
    except CkptError as e:
        # the checkpoint exit-code contract: every resume defect (bad
        # path, module mismatch, truncation, checksum failure) is ONE
        # actionable line on stderr and exit 2 — never a traceback,
        # never a silently-wrong resume
        print(f"error: {e}", file=sys.stderr)
        return 2
    except FileNotFoundError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    except Exception as e:
        print(f"error: {type(e).__name__}: {e}", file=sys.stderr)
        if os.environ.get("JAXMC_DEBUG"):
            raise
        return 2


if __name__ == "__main__":
    sys.exit(main())
