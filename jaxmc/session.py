r"""CheckSession: the reusable check flow as explicit, resumable stages.

ISSUE 7's forcing-function refactor: cli.py's monolithic check flow —
cfg sniffing, model load, device init, engine construction, search,
fallback — becomes one object with three named stages,

    parse    cfg + spec  ->  a bound Model (or an ASSUME-mode verdict)
    analyze  Model       ->  lint diagnostics (ISSUE 9; gated by
                             cfg.analyze off/warn/strict — strict
                             raises AnalyzeError on error diagnostics
                             before any compile cost is paid)
    compile  Model       ->  a ready engine (device init, kernel build;
                             carries the layout signature when the jax
                             backend compiled one)
    explore  engine      ->  CheckResult (re-runnable: warm re-checks
                             override resume/checkpoint per run)

so the CLI `check` command (a thin driver with byte-identical output),
the serve daemon (`python -m jaxmc.serve`, which holds sessions WARM and
answers repeat submissions from their checkpoints), and tests all drive
the same code.  A session carries exactly the state the daemon needs to
amortize: the parsed model, the built engine (whose jit caches are the
expensive warm artifact), the layout signature (the durable-artifact
key: compile cache entries and capacity profiles are keyed by
(module, layout_sig)), and the checkpoint handle.  Telemetry rides the
session: every stage reports spans into the recorder the session was
built with (obs.current() at construction unless one is passed).

Stage errors propagate as the same exceptions the CLI always mapped
(ModeError/CompileError/CkptError/ImportError/device failures) — the
DRIVER owns the policy (cli.py prints + exit codes; the serve daemon
marks the job failed; `demote_to_cpu` implements the shared device->CPU
fallback either driver can invoke).
"""

from __future__ import annotations

import os
import sys
import time
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

from . import obs
from .compile.vspec import Bounds


def read_text(path: str) -> str:
    """Read a cfg/spec file WITHOUT leaking the handle (the old
    `open(...).read()` pattern relied on refcount finalization)."""
    with open(path, encoding="utf-8", errors="replace") as fh:
        return fh.read()


def default_cfg_path(spec_path: str) -> Optional[str]:
    guess = os.path.splitext(spec_path)[0] + ".cfg"
    return guess if os.path.exists(guess) else None


def load_model(spec_path: str, cfg_path, no_deadlock: bool,
               includes=()):
    from .front.cfg import parse_cfg, ModelConfig
    from .sem.modules import Loader, bind_model

    if cfg_path is None:
        cfg_path = default_cfg_path(spec_path)
    if cfg_path:
        cfg = parse_cfg(read_text(cfg_path))
    else:
        cfg = ModelConfig(specification="Spec")
    if no_deadlock:
        cfg.check_deadlock = False
    ldr = Loader([os.path.dirname(os.path.abspath(spec_path))] +
                 list(includes))
    mod = ldr.load_path(spec_path)
    return bind_model(mod, cfg)


_SENTINEL = object()  # "keep the configured value" for explore overrides


def _ask_host_devices(jax, n: int) -> None:
    """XLA:CPU comes up with one device unless told otherwise, BEFORE
    the backend exists: ask for `n` (tests, `--rehearse-on-cpu`) unless
    XLA_FLAGS already grants as many.  A backend that is already up
    keeps its count; device_init then compares it with `n`."""
    import re
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                  os.environ.get("XLA_FLAGS", ""))
    if m and int(m.group(1)) >= n:
        return
    try:
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:
        pass  # backends are initialized: the count check speaks


class AnalyzeError(Exception):
    """--analyze=strict found error-severity diagnostics: the run must
    not proceed to compile/search (exit 2 on the CLI, a rejected job on
    the serve daemon).  Carries the full diagnostic list so drivers can
    render every finding, not only the first."""

    def __init__(self, diagnostics):
        self.diagnostics = list(diagnostics)
        errs = [d for d in self.diagnostics if d.severity == "error"]
        super().__init__(
            f"{len(errs)} error diagnostic"
            f"{'s' if len(errs) != 1 else ''} "
            f"({'; '.join(d.code for d in errs[:6])})")


@dataclass
class SessionConfig:
    """Everything a check run is parameterized by — field names and
    defaults mirror the `check` CLI exactly (argparse populates the same
    surface), plus the serve-only knobs at the bottom."""

    spec: str
    cfg: Optional[str] = None
    include: Tuple[str, ...] = ()
    backend: str = "interp"
    platform: Optional[str] = None
    max_states: Optional[int] = None
    workers: Optional[int] = None
    no_deadlock: bool = False
    no_device_fallback: bool = False
    progress_every: float = 30.0
    seq_cap: int = Bounds.seq_cap
    grow_cap: int = Bounds.grow_cap
    kv_cap: int = Bounds.kv_cap
    no_trace: bool = False
    host_seen: bool = False
    sample: Tuple[int, int, int] = (800, 40, 60)
    chunk: int = 2048
    resident: bool = False
    # hierarchical seen set (ISSUE 12): dedup-key mode ("auto" keeps
    # the width-based default; "fingerprint" trades exact keys for
    # 128-bit fingerprints — 4-8x the states per tier, collision
    # probability reported; "exact" refuses to fingerprint), the
    # device seen cap (key rows; overflow spills to host/disk tiers
    # instead of growing; env JAXMC_SEEN_CAP), and the disk-tier
    # spill directory (default: a temp dir)
    seen: str = "auto"
    seen_cap: Optional[int] = None
    seen_spill: Optional[str] = None
    checkpoint: Optional[str] = None
    checkpoint_every: float = 600.0
    resume: Optional[str] = None
    # static analysis (ISSUE 9): lint severity gate for the analyze
    # stage — "off" (skip), "warn" (print diagnostics, continue),
    # "strict" (error diagnostics abort with exit 2 before compile)
    analyze: str = "off"
    # partial-order reduction (ISSUE 15 interp, ISSUE 18 device;
    # opt-in): expand one globally-commuting invisible arm per state
    # instead of every enabled arm — preserves invariant/deadlock
    # verdicts, NOT raw counts.  On device backends the ample mask is
    # applied INSIDE the fused step (zero extra dispatches); configs
    # the device mask cannot serve (hybrid demotions, symmetry, ...)
    # run unreduced with a named warning, never a silent engine swap.
    por: bool = False
    # device profiling mode (ISSUE 17, obs/prof.py): None (cheap
    # counters only), "wall" or "xla".  Plumbing, not an answer-changer
    # — deliberately NOT part of job_signature_fields (profiling never
    # changes counts or traces)
    profile: Optional[str] = None
    # serve-only knobs (no CLI flags):
    final_checkpoint: bool = False  # checkpoint COMPLETED runs too —
    # the daemon's warm-resume source
    # the ONE capacity field: the resident engine's profile keys (SC,
    # FCap, AccCap, VC) or, with devices > 1, the mesh profile's (SC,
    # FC, TRL, GAM16, MSL, VC — per shard)
    res_caps: Optional[Dict[str, int]] = None
    # device count (ISSUE 26): None/1 = the one-chip engines; N > 1
    # shards the frontier and the seen set over the first N devices of
    # the platform (backend/mesh.py).  Never fewer shards than asked:
    # device_init raises when fewer than N devices are visible.
    devices: Optional[int] = None

    @classmethod
    def from_args(cls, args) -> "SessionConfig":
        """Build from an argparse Namespace (the `check` subcommand's);
        unknown session-only fields keep their defaults."""
        import dataclasses
        kw = {}
        for f in dataclasses.fields(cls):
            if hasattr(args, f.name):
                kw[f.name] = getattr(args, f.name)
        kw["include"] = tuple(getattr(args, "include", ()) or ())
        kw["sample"] = tuple(getattr(args, "sample", (800, 40, 60)))
        return cls(**kw)

    def job_signature_fields(self) -> Dict[str, Any]:
        """The option surface that makes two submissions 'the same job'
        for warm reuse: anything that changes the search's RESULT or its
        layout/kernels.  Checkpoint/resume paths, telemetry, and pacing
        knobs (progress_every, checkpoint_every) are excluded — they
        change the run's plumbing, not its answer."""
        f = {
            "spec": self.spec, "cfg": self.cfg,
            "include": list(self.include), "backend": self.backend,
            "platform": self.platform, "max_states": self.max_states,
            "no_deadlock": self.no_deadlock,
            "seq_cap": self.seq_cap, "grow_cap": self.grow_cap,
            "kv_cap": self.kv_cap, "no_trace": self.no_trace,
            "host_seen": self.host_seen, "sample": list(self.sample),
            "chunk": self.chunk, "resident": self.resident,
            "seen": self.seen, "seen_cap": self.seen_cap,
            "por": self.por,
        }
        if self.n_devices > 1:
            # only a sharded job carries the key: the one-chip jobs'
            # signatures (and the checkpoints keyed by them) stay what
            # they were before the option existed
            f["devices"] = self.n_devices
        return f

    @property
    def n_devices(self) -> int:
        """The shard count: 1 for the one-chip engines (None and 1 are
        the same job)."""
        return max(1, int(self.devices or 1))

    def batch_signature_fields(self) -> Dict[str, Any]:
        """job_signature_fields WITHOUT the model identity: the option
        surface every member of a cross-model vmapped batch must share
        (per-model differences ride the lifted constant lanes)."""
        f = self.job_signature_fields()
        f.pop("spec", None)
        f.pop("cfg", None)
        return f


def _stable(v) -> str:
    """Deterministic rendering of a parsed cfg constant value (repr of
    frozensets is insertion-ordered — sort them)."""
    if isinstance(v, frozenset):
        return "{" + ",".join(sorted(_stable(x) for x in v)) + "}"
    return repr(v)


@dataclass
class BatchProfile:
    """Parse-time batch compatibility verdict for one submission
    (ISSUE 13): the LAYOUT-COMPAT CLASS key plus the scheduling cost
    estimate — both derived before any engine exists."""
    bsig: str                      # equal <=> layout-compatible, i.e.
    # one vmapped engine can serve both jobs
    lift: Tuple[str, ...]          # constants that become batch lanes
    cost_estimate: Optional[int]   # analyze's state-space estimate
    # (None = analysis bailed: no fast-lane routing)


def batch_profile(cfg: SessionConfig,
                  model=None) -> Optional["BatchProfile"]:
    """Prove (at parse time) which layout-compat class this job belongs
    to.  Two submissions with equal `bsig` differ at most in LIFTABLE
    constant values — same module shape, same non-lifted constants,
    same cfg-declared predicates, same result-affecting options — so
    the serve fleet may run them through one vmapped device program
    (backend/batch.py).  Returns None for configurations the batcher
    does not cover (interp backend, resident mode, non-host_seen device
    modes, tiered seen sets) or when the model fails to load — the job
    then schedules solo, exactly as before."""
    import hashlib
    import json
    if cfg.backend == "interp" or cfg.resident or not cfg.host_seen \
            or cfg.seen_cap is not None or cfg.por or cfg.n_devices > 1:
        return None
    if model is None:
        try:
            model = load_model(cfg.spec, cfg.cfg, cfg.no_deadlock,
                               cfg.include)
        except Exception:  # noqa: BLE001 — an unloadable pair is simply
            # not batchable; the solo path reports the real error
            return None
    from .analyze.bounds import liftable_constants, state_space_estimate
    lift = liftable_constants(model)
    mc = model.cfg
    masked = {n: ("<lifted>" if n in lift else _stable(v))
              for n, v in sorted(mc.constants.items())}
    ident = {
        "module": model.module.name,
        "vars": list(model.vars),
        "spec_sha": hashlib.sha256(
            read_text(cfg.spec).encode()).hexdigest(),
        "cfg_shape": {
            "specification": mc.specification, "init": mc.init,
            "next": mc.next,
            "invariants": sorted(mc.invariants),
            "properties": sorted(mc.properties),
            "constraints": sorted(mc.constraints),
            "action_constraints": sorted(mc.action_constraints),
            "symmetry": mc.symmetry, "view": mc.view,
            "overrides": sorted(mc.overrides.items()),
            "scoped_overrides": sorted(
                (f"{k[0]}!{k[1]}", v)
                for k, v in mc.scoped_overrides.items()),
            "check_deadlock": mc.check_deadlock,
            "constants": masked,
        },
        "lift": list(lift),
        "options": cfg.batch_signature_fields(),
    }
    blob = json.dumps(ident, sort_keys=True).encode()
    bsig = "b" + hashlib.sha256(blob).hexdigest()[:15]
    try:
        est = state_space_estimate(model)
    except Exception:  # noqa: BLE001 — estimation must never block
        est = None
    return BatchProfile(bsig=bsig, lift=lift, cost_estimate=est)


class CheckSession:
    """One check as three resumable stages over one model/engine pair.

    Stage order is enforced (compile needs parse's model, explore needs
    compile's engine); each stage is idempotent — calling it again when
    already complete is a no-op, so a driver can `ensure()` its way to
    any stage.  `explore` alone is deliberately RE-runnable with
    per-run overrides: the serve daemon re-drives a warm session's
    engine with `resume_from=<previous job's final checkpoint>` and the
    search replays the stored verdict without recompiling anything."""

    def __init__(self, cfg: SessionConfig, tel=None, log=None):
        self.cfg = cfg
        self.tel = tel if tel is not None else obs.current()
        self.log = log if log is not None else obs.Logger(quiet=True)
        self.stage: Optional[str] = None  # last COMPLETED stage
        self.kind: Optional[str] = None   # "model" | "assumes"
        self.model = None
        self.engine = None
        self.cache_dir: Optional[str] = None  # persistent compile cache
        self.layout_sig: Optional[str] = None
        self.result = None
        # the engine that produced `result`: cfg.backend unless the run
        # demoted (demote_to_cpu) — what the rate line and the result
        # block name
        self.finished_on = cfg.backend
        self.explore_count = 0
        self.diagnostics = None  # analyze stage output (lint findings)

    # ---- stage: parse -------------------------------------------------
    def parse(self) -> str:
        """Load cfg+spec.  Returns the session kind: "model" (a bound
        Model ready to compile) or "assumes" (TLC's No-Behavior-Spec
        calculator mode — drive it with run_assumes())."""
        if self.stage is not None:
            return self.kind
        cfg = self.cfg
        cfgp = cfg.cfg or default_cfg_path(cfg.spec)
        self.cfg_path = cfgp
        if cfgp:
            from .front.cfg import parse_cfg
            c = parse_cfg(read_text(cfgp))
            if not c.specification and not c.init:
                self.kind = "assumes"
                self.stage = "parse"
                return self.kind
        with self.tel.span("load", spec=cfg.spec):
            self.model = load_model(cfg.spec, cfg.cfg, cfg.no_deadlock,
                                    cfg.include)
        # ISSUE 16: hang a search-progress estimator off the recorder —
        # the analyze bound (when one exists) turns every progress line,
        # heartbeat and /status poll into a fraction-explored + ETA
        obs.attach_estimator(self.tel, self.model)
        self.kind = "model"
        self.stage = "parse"
        return self.kind

    def run_assumes(self) -> int:
        """TLC's "No Behavior Spec" mode: evaluate the module's ASSUMEs
        as a calculator / unit-test harness (SimpleMath.cfg:4-11,
        PrintValues.tla — SURVEY.md §4.4).  Prints the verdict lines
        (the CLI contract); returns the exit code."""
        assert self.kind == "assumes", "run_assumes needs an assumes session"
        from .front.cfg import parse_cfg, ModelConfig
        from .sem.modules import Loader, bind_model_defs
        from .sem.eval import Ctx, eval_expr
        from .sem.values import fmt

        cfg = self.cfg
        mcfg = parse_cfg(read_text(self.cfg_path)) if self.cfg_path \
            else ModelConfig()
        ldr = Loader([os.path.dirname(os.path.abspath(cfg.spec))] +
                     list(cfg.include))
        mod = ldr.load_path(cfg.spec)
        defs = bind_model_defs(mod, mcfg)
        prints = []
        ctx = Ctx(defs, {}, None, None, (),
                  on_print=lambda v: prints.append(v))
        failed = 0
        for a in mod.assumes:
            v = eval_expr(a.expr, ctx)
            nm = a.name or "ASSUME"
            if v is not True:
                print(f"Assumption {nm} is violated (evaluated to "
                      f"{fmt(v)}).")
                failed += 1
        for v in prints:
            print(fmt(v) if not isinstance(v, str) else v)
        if failed:
            return 1
        print(f"{len(mod.assumes)} assumption"
              f"{'s' if len(mod.assumes) != 1 else ''} checked. "
              "No error has been found.")
        return 0

    # ---- stage: analyze -----------------------------------------------
    def analyze(self):
        """The static-analysis stage between parse and compile (ISSUE
        9): lint the spec/cfg pair and store the diagnostics.  Severity
        policy follows cfg.analyze — "off" skips entirely (stage chain
        passes through), "warn" records, "strict" raises AnalyzeError
        when any error-severity diagnostic exists.  Idempotent like the
        other stages — and deliberately runnable BEFORE parse: the
        linter re-loads the pair itself, so a cfg broken in a way that
        makes bind_model refuse (an undefined invariant name, an
        unassigned CONSTANT) still gets its diagnostics reported
        instead of a bare parse error.  Assumes-mode pairs (no behavior
        spec) have nothing to analyze."""
        mode = (self.cfg.analyze or "off").lower()
        if self.diagnostics is not None:
            if mode == "strict":
                errs = [d for d in self.diagnostics
                        if d.severity == "error"]
                if errs:
                    # the strict refusal must hold on EVERY call — a
                    # driver that caught the first AnalyzeError cannot
                    # compile/explore its way past it via the stage
                    # chain (compile() re-enters here)
                    raise AnalyzeError(self.diagnostics)
            return self.diagnostics
        if mode == "off":
            return []
        cfgp = self.cfg.cfg or default_cfg_path(self.cfg.spec)
        if cfgp:
            try:
                from .front.cfg import parse_cfg
                c = parse_cfg(read_text(cfgp))
                if not c.specification and not c.init:
                    return []  # assumes-mode: no model to lint
            except Exception:
                pass  # unparseable cfg: lint_pair reports it as JMC100
        from .analyze.lint import errors, lint_pair, max_severity
        with self.tel.span("analyze", mode=mode):
            diags = lint_pair(self.cfg.spec, cfgp,
                              tuple(self.cfg.include))
        self.diagnostics = diags
        if diags:
            self.tel.counter("analyze.lint_diags", len(diags))
            self.tel.gauge("analyze.lint_max_severity",
                           max_severity(diags))
            self.tel.gauge("analyze.lint_codes",
                           sorted({d.code for d in diags}))
        if self.stage == "parse":
            self.stage = "analyze"
        if mode == "strict" and errors(diags):
            raise AnalyzeError(diags)
        return diags

    # ---- stage: compile -----------------------------------------------
    def resolve_platform(self) -> Optional[str]:
        """The jax platform this session's device backend should pin
        (ISSUE 11).  `--backend cpu|gpu|tpu` names it outright;
        `--backend auto` asks the preflight oracle (jaxmc/backend/
        oracle.py — tiny compile+dispatch probe per visible platform,
        seconds, hang-proof) and records the verdict in telemetry;
        `--backend jax` means --platform / JAXMC_PLATFORM if given, else
        whatever jax initializes (JAX_PLATFORMS included)."""
        b = self.cfg.backend
        if b in ("cpu", "gpu", "tpu"):
            return b
        if b == "auto":
            from .backend.oracle import preflight
            with self.tel.span("preflight_oracle"):
                v = preflight(tel=self.tel)
            if v["platform"] is None:
                errs = "; ".join(
                    f"{p}: {pr.get('error')}"
                    for p, pr in v["probes"].items())
                raise RuntimeError(
                    f"backend oracle found no live platform ({errs})")
            self.log(f"-- backend oracle: {v['platform']} "
                     f"({v['reason']}; {v['wall_s']}s)")
            return v["platform"]
        return self.cfg.platform

    def device_init(self) -> Optional[str]:
        """Device init with bounded retries + backoff
        (JAXMC_DEVICE_RETRIES, default 2) for a transient runtime
        failure.  When the retries run out the error PROPAGATES: there
        is no progress to save yet, so nothing here falls back to the
        CPU — `check` exits 2 naming the platform, a served job fails.
        ImportError (jax not in the build) stays terminal — retrying
        cannot install a wheel.  Returns the persistent compile-cache
        dir (None when opted out or fallen back cold)."""
        from . import faults
        cfg, tel = self.cfg, self.tel
        platform = self.resolve_platform()  # oracle verdict is cached
        retries = int(os.environ.get("JAXMC_DEVICE_RETRIES", "2"))
        for attempt in range(retries + 1):
            try:
                with tel.span("device_init",
                              platform=platform or "default",
                              attempt=attempt):
                    import jax
                    faults.inject("device_init_fail")
                    if platform:
                        jax.config.update("jax_platforms", platform)
                    if cfg.n_devices > 1 and platform in (None, "cpu"):
                        _ask_host_devices(jax, cfg.n_devices)
                    # persistent XLA compile cache, every device run
                    # (compile/cache.py resolves where it lives):
                    # GUARDED — a wedged, corrupt or foreign-build cache
                    # degrades to cold compilation instead of hanging
                    # the run
                    from .compile.cache import enable_guarded_cache
                    cache_dir = enable_guarded_cache(tel=tel)
                    # device init happens HERE, inside the span: a
                    # missing or hung device is attributed to
                    # device_init, not compile
                    devs = jax.devices()
                    if platform and devs[0].platform != platform:
                        raise RuntimeError(
                            f"asked for platform {platform!r} but jax "
                            f"initialized {devs[0].platform!r}")
                    obs.stamp_device(tel, devs)
            except (faults.FaultInjected, RuntimeError, OSError,
                    ConnectionError) as ex:
                if attempt >= retries:
                    raise RuntimeError(
                        f"device init failed for platform "
                        f"{platform or 'default'!r}: {ex}") from ex
                tel.counter("device.init_retries")
                print(f"warning: device init failed ({ex}); retrying "
                      f"({attempt + 1}/{retries})", file=sys.stderr)
                time.sleep(min(0.2 * (2 ** attempt), 5.0))
                continue
            if len(devs) < cfg.n_devices:
                # not transient, so outside the retries: never fewer
                # shards than asked, never the one-chip engine instead
                raise RuntimeError(
                    f"device init failed for platform "
                    f"{devs[0].platform!r}: --devices {cfg.n_devices} "
                    f"asked, {len(devs)} visible (a sharded run on "
                    f"fewer devices gives no result)")
            return cache_dir

    def compile(self) -> "CheckSession":
        """Build the engine for the configured backend.  For the jax
        backend this is the expensive stage (device init, layout
        sampling, per-arm kernel construction) and the one whose product
        the serve daemon keeps warm; it also stamps `layout_sig`, the
        key under which compile-cache entries and capacity profiles
        persist.  Raises what engine construction raises (ModeError /
        CompileError / device failures) — the driver owns the policy."""
        if self.stage in ("compile", "explore"):
            return self
        if self.stage is None:
            self.parse()
        if self.stage == "parse":
            self.analyze()  # no-op when cfg.analyze == "off"
        assert self.kind == "model", "assumes sessions have no engine"
        cfg = self.cfg
        if cfg.backend == "interp":
            if cfg.n_devices > 1:
                from .compile.vspec import ModeError
                raise ModeError(
                    f"--devices {cfg.n_devices} needs a device backend "
                    f"(--backend jax/tpu/...): the interpreter shards "
                    f"nothing - drop --devices or use --workers")
            from .engine.parallel import ParallelExplorer, default_workers
            # None or 0 = auto (JAXMC_WORKERS, else min(cpu_count, 8))
            self.workers = default_workers() if not cfg.workers \
                else max(1, cfg.workers)
            kw = dict(log=self.log, max_states=cfg.max_states,
                      progress_every=cfg.progress_every,
                      checkpoint_path=cfg.checkpoint,
                      checkpoint_every=cfg.checkpoint_every,
                      resume_from=cfg.resume,
                      final_checkpoint=cfg.final_checkpoint)
            if cfg.por:
                # the ample-set choice depends on the live seen-set, a
                # per-state sequential decision — the fork-pool's
                # chunked expansion cannot replay it; serial engine,
                # named reason
                if self.workers > 1:
                    self.tel.gauge("parallel.fallback_reason", "por")
                self.workers = 1
                from .engine.explore import Explorer
                self.engine = Explorer(self.model, por=True, **kw)
            elif self.workers > 1:
                # worker-parallel frontier expansion (crash-safe:
                # checkpoints natively, survives worker deaths); falls
                # back to the serial engine (identical results) only for
                # stepwise refinement or when the platform cannot fork
                self.engine = ParallelExplorer(self.model,
                                               workers=self.workers, **kw)
            else:
                from .engine.explore import Explorer
                self.engine = Explorer(self.model, **kw)
        else:
            self.cache_dir = self.device_init()
            bounds = Bounds(seq_cap=cfg.seq_cap, grow_cap=cfg.grow_cap,
                            kv_cap=cfg.kv_cap)
            kw = dict(log=self.log, bounds=bounds,
                      store_trace=not cfg.no_trace,
                      progress_every=cfg.progress_every,
                      host_seen=cfg.host_seen,
                      chunk=cfg.chunk,
                      resident=cfg.resident,
                      sample_cfg=tuple(cfg.sample),
                      checkpoint_path=cfg.checkpoint,
                      checkpoint_every=cfg.checkpoint_every,
                      resume_from=cfg.resume,
                      max_states=cfg.max_states,
                      por=cfg.por,
                      final_checkpoint=cfg.final_checkpoint,
                      seen_mode=cfg.seen,
                      seen_cap=cfg.seen_cap,
                      spill_dir=cfg.seen_spill)
            with self.tel.span("engine_build"):
                if cfg.n_devices > 1:
                    # the sharded engine over the first N devices; what
                    # it cannot honour it refuses by name (ModeError)
                    import jax
                    import numpy as np
                    from jax.sharding import Mesh
                    from .backend.mesh import MeshExplorer
                    mesh = Mesh(np.array(jax.devices()[:cfg.n_devices]),
                                ("d",))
                    self.engine = MeshExplorer(
                        self.model, mesh=mesh, mesh_caps=cfg.res_caps,
                        **kw)
                else:
                    from .backend.bfs import TpuExplorer
                    self.engine = TpuExplorer(
                        self.model, res_caps=cfg.res_caps, **kw)
            self.layout_sig = self.engine._layout_sig()
        self.stage = "compile"
        return self

    # ---- stage: explore -----------------------------------------------
    def explore(self, resume_from=_SENTINEL, checkpoint_path=_SENTINEL,
                final_checkpoint=_SENTINEL):
        """Run (or RE-run) the search.  Overrides apply to this run only
        in spirit — they are set on the engine, whose run() reads them
        fresh each call — and are how a warm session answers a repeat
        submission: explore(resume_from=last_final_checkpoint) replays
        the completed search's verdict through the already-compiled
        kernels.  Returns (and stores) the CheckResult."""
        if self.stage in (None, "parse", "analyze"):
            self.compile()
        ex = self.engine
        if resume_from is not _SENTINEL:
            ex.resume_from = resume_from
        if checkpoint_path is not _SENTINEL:
            ex.checkpoint_path = checkpoint_path
        if final_checkpoint is not _SENTINEL:
            ex.final_checkpoint = final_checkpoint
        self.explore_count += 1
        if self.cfg.backend == "interp":
            with self.tel.request("search", workers=self.workers):
                self.result = ex.run()
        else:
            with self.tel.request("search"):
                self.result = ex.run()
            from .compile.cache import record_entries_end
            record_entries_end(self.cache_dir)
        self.stage = "explore"
        return self.result

    # ---- shared device->CPU fallback ----------------------------------
    def demote_to_cpu(self, err) -> Any:
        """Terminal device failure MID-SEARCH -> the parallel CPU
        engine, resuming from the device run's host snapshot
        (`<checkpoint>.host`, written at level barriers by
        backend/bfs.py).  The fallback exists to save progress: where
        no snapshot exists there is none to save and falling back would
        only hide the fault (a missing chip, a refused compile, an HBM
        OOM reported as a pass), so `err` is RE-RAISED — the one rule
        for every driver (cli.py, serve/owner.py, serve/daemon.py).
        The demotion is machine-readable: `device.demoted` gauge +
        event (flagged by `python -m jaxmc.obs diff`), `finished_on`,
        and a result warning on stdout."""
        from .engine.parallel import ParallelExplorer, default_workers
        cfg, tel = self.cfg, self.tel
        snap = (cfg.checkpoint + ".host") if cfg.checkpoint else None
        if not (snap and os.path.exists(snap)):
            raise err
        reason = f"{type(err).__name__}: {err}"
        print(f"warning: device backend failed terminally ({reason}); "
              f"falling back to the parallel CPU engine", file=sys.stderr)
        tel.event("device.demoted", reason=reason)
        tel.gauge("device.demoted", reason[:200])
        tel.counter("device.demotions")
        print(f"resuming from host snapshot {snap}", file=sys.stderr)
        workers = default_workers() if not cfg.workers \
            else max(1, cfg.workers)
        with tel.span("search_fallback", workers=workers):
            res = ParallelExplorer(
                self.model, workers=workers, log=self.log,
                max_states=cfg.max_states,
                progress_every=cfg.progress_every,
                checkpoint_path=snap,
                checkpoint_every=cfg.checkpoint_every,
                resume_from=snap,
                final_checkpoint=cfg.final_checkpoint).run()
        res.warnings.append(
            f"device backend failed ({reason}); the run completed on the "
            f"parallel CPU engine, resumed from the last host snapshot")
        self.finished_on = "interp"
        self.result = res
        return res

    # ---- introspection -------------------------------------------------
    def describe(self) -> Dict[str, Any]:
        """The session's resumable identity (serve status endpoint)."""
        return {
            "stage": self.stage,
            "kind": self.kind,
            "backend": self.cfg.backend,
            "spec": self.cfg.spec,
            "module": self.model.module.name if self.model is not None
            else None,
            "layout_sig": self.layout_sig,
            "checkpoint": self.cfg.checkpoint,
            "explore_count": self.explore_count,
            "analyze_diags": len(self.diagnostics)
            if self.diagnostics is not None else None,
        }
