r"""The `--metrics-out` / `--trace` event schema, as data.

One place pins what every artifact must carry so the CLI, the harnesses, the
sweep driver, and tests/test_obs.py agree. `validate_summary` raises
ValueError with the missing/ill-typed field names — it is deliberately
structural (required keys + types + level-index monotonicity), not
exhaustive: engines are free to add fields.

Trace JSONL event grammar (one JSON object per line, `ev` discriminates;
since jaxmc.metrics/3 every event also carries `tid` — the fleet-wide
trace id, obs/context.py):

  proc_meta  {t, mono, pid, argv, psid, parent_span, env}
                                           -- per-file header (first
                                              line): process identity +
                                              span lineage + monotonic
                                              clock anchor
  run_start  {t, meta}
  span_open  {name, t, parent, attrs[, id, parent_id]}  -- partial-
  span       {name, t0, wall_s, attrs[, id, parent_id, error]}  span
                                              forensics; ids count
                                              spans per recorder
  level      {level, t, frontier?, generated?, new?, distinct?, ...}
  heartbeat  {t, wall_s, rss_bytes, open_spans, last_level,
              progress_seq}                -- periodic watchdog beat
  stall      {t, stalled_for_s, threshold_s, open_spans, last_level,
              median_level_s}              -- watchdog: no span/level
                                              progress for too long
  counter/gauge changes are rolled up in the summary only
  log        {t, msg}                      -- mirror of the stdout line
  run_end    {t}

Summary (metrics-out) required surface: see REQUIRED_KEYS below; each
phases[i] carries {name, wall_s, count} (+optional open=True for spans
still running at rollup — the deadline-blowout record); each levels[i]
carries at least {level} with non-decreasing level indices.

Schema history (additive — every jaxmc.metrics/1 artifact is a valid
jaxmc.metrics/2 artifact minus the new optional surface, so readers and
`validate_summary` accept both):

  jaxmc.metrics/1  (PR 1) the surface above minus heartbeat/stall.
  jaxmc.metrics/2  (PR 2) adds, all optional:
    - meta block `env` = {jax_version, platform, device_count}: the
      environment fingerprint `python -m jaxmc.obs diff` uses to
      attribute regressions to environment changes;
    - trace events `heartbeat` / `stall` (jaxmc/obs/watchdog.py);
    - compile-introspection gauges: `compile.arm_cost` ({arm label ->
      {jaxpr_eqns, hlo_flops?, hlo_bytes?}}), counters
      `compile.jaxpr_eqns_total`, `compile.hlo_flops_total`,
      `compile.hlo_bytes_total`, and jit-cache effectiveness counters
      `compile.cache_hits` / `compile.cache_misses`;
    - watchdog counters `watchdog.heartbeats` / `watchdog.stalls` and
      the `watchdog.max_stall_s` high-water gauge.

  (PR 3, still jaxmc.metrics/2 — all additive/optional:)
    - parallel exact engine (engine/parallel.py): level records may
      carry `workers`, `chunk_wall_s` (summed worker expansion wall for
      the level) and `merge_wall_s` (parent-side merge wall); gauges
      `parallel.workers` / `parallel.fallback_reason`, counter
      `parallel.chunks`, trace event `parallel.fallback {reason}`;
    - persistent XLA compile cache (compile/cache.py): counters
      `compile.persistent_cache_hits` (and any other
      /jax/compilation_cache/* monitoring events, same naming), gauges
      `compile.persistent_cache_dir`,
      `compile.persistent_cache_entries_start` / `_end`,
      `compile.persistent_cache_active`;
    - checkpoint cost: phase `checkpoint.write` (span attrs: states,
      queue) — checkpoint wall no longer hides inside `search`.

  (PR 4, still jaxmc.metrics/2 — all additive/optional; the
   fault-tolerance surface:)
    - crash-safe parallel engine (engine/parallel.py): counters
      `parallel.worker_deaths` / `parallel.respawns` /
      `parallel.requeues` / `parallel.chunk_retries` /
      `parallel.degradations`, gauges `parallel.degraded` (the reason
      string — present ONLY when the run fell back to serial expansion
      after exhausting its retry budget) and `parallel.pool_size`
      (post-shrink worker count), trace events `parallel.worker_death
      {level, pids, lost_chunks}` / `parallel.chunk_error {level,
      chunk, error, retry}` / `parallel.degraded {reason}`;
    - device retry/demotion (cli.py): counters `device.init_retries` /
      `device.demotions` / `compile.retries`, gauge `device.demoted`
      (the terminal failure reason — `python -m jaxmc.obs diff` raises
      a REGRESS flag when it appears between runs), trace event
      `device.demoted {reason}`, phase `search_fallback`;
    - checkpoint integrity (engine/ckpt.py): phase
      `checkpoint.host_snapshot` + counter `checkpoint.host_snapshots`
      (the device path's CPU-resumable `<checkpoint>.host` snapshot);
    - fault harness (jaxmc/faults.py): counter `faults.injected`,
      trace event `fault.injected {site, ...ctx}` — present only when
      JAXMC_FAULTS is set (chaos runs / `make chaos`).

  (PR 5, still jaxmc.metrics/2 — all additive/optional; the
   compile-amortization surface:)
    - guarded persistent compile cache (compile/cache.py): gauge
      `compile.persistent_cache_guard` — "ok" / "ok (<notes>)" when the
      cache enabled (notes name quarantined entries / a fresh probe),
      "cold-fallback:<reason>" when the guard degraded the run to cold
      compilation (wedged probe, foreign build — the reason says so
      and the dir is left untouched), "disabled:..." on explicit
      opt-out; counters
      `compile.persistent_cache_fallbacks` and
      `compile.persistent_cache_quarantines`.  The existing
      `compile.persistent_cache_hits` counter is the CROSS-PROCESS
      proof: >0 means this process reloaded a program some other
      process compiled.
    - expansion-mode pins (corpus.py): sweep case records/details note
      `[mode pinned]` for manifest-pinned interp-arms cases (kernel
      construction skipped) and carry a per-arm demotion reason table
      (`[demoted arms: <label>: <reason>; ...]`) whenever arms demote
      unpinned; a pinned case that slides toward the interpreter is a
      FAIL with detail "REGRESSION: expansion mode slid ...".
    - symmetry disclosure is three-way: `sym=device-reduced (<form>)`,
      `sym=identity` (identity permutation group — no divergence), or
      `sym=UNREDUCED-FALLBACK (...)` (a genuine CompileError fallback;
      the only case where counts diverge from TLC's reduced ones).

  (PR 6, still jaxmc.metrics/2 — all additive/optional; the
   state-encoding surface:)
    - bit-packed lane plans (compile/pack.py): gauges
      `layout.packed_width_lanes` (packed row width, vs the existing
      `layout.width_lanes`), `layout.bits_per_state`,
      `layout.pack_ratio` (packed/unpacked width),
      `layout.pack_guarded_lanes` (observed-range int lanes with a
      runtime guard), and `dedup.mode` — "exact" | "fp128" with a
      "-packed" suffix when the key basis is the packed row or
      "-view" when cfg VIEW keys the dedup;
    - buffer donation (backend/bfs.py): gauge `device.donation` (bool —
      seen/frontier donated into the jitted steps; off on XLA:CPU by
      default, JAXMC_DONATE forces);
    - capacity profiles (compile/cache.py): gauge `profile.status` —
      "loaded" / "saved" / "absent" / "disabled:..." /
      "degraded:<named reason>" (stale layout signature, foreign
      schema, module mismatch, unreadable, malformed caps — a degraded
      profile falls back to the overflow-growth path, never a crash);
      counters `profile.hits` / `profile.saves` / `profile.degrades`.

  (PR 7, still jaxmc.metrics/2 — all additive/optional; the
   checking-as-a-service surface:)
    - cooperative drain (jaxmc/drain.py): `result.drained` = true when
      a SIGTERM/daemon drain stopped the search at a safe boundary
      (implies `result.truncated`; the run checkpointed and is
      resumable); trace event `drain {reason, engine}`.
    - serve fleet telemetry (jaxmc/serve/daemon.py, the daemon's own
      Telemetry): per-job `job` phase spans (attrs: id, sig, spec,
      backend, batched), gauges `serve.queue_depth` / `serve.running` /
      `serve.warm_sessions` / `serve.workers` / `serve.draining`,
      counters `serve.jobs_submitted` / `serve.jobs_done` /
      `serve.jobs_failed` / `serve.jobs_drained` / `serve.warm_hits`
      (a repeat submission answered by a warm session's checkpoint
      replay) / `serve.cold_runs` / `serve.ckpt_resumes` (cold engine,
      but resumed a previous daemon life's checkpoint) /
      `serve.batched_jobs` (queued identical jobs coalesced into one
      dispatch) / `serve.requeued_on_start`; trace events
      `serve.drain {reason}` / `serve.job_failed {id, error}`.
    - serve per-job artifacts (`<spool>/results/<id>.json`): ordinary
      jaxmc.metrics/2 summaries (meta `command` = "serve.job") plus a
      top-level `serve` block {sig, warm_engine,
      resumed_from_checkpoint, window_recompiles (count of
      `fresh_compile` level records — 0 on a warm hit), profile_hits,
      persistent_cache_hits, batched_with, job_wall_s}; violating jobs
      add `result.trace` (the rendered counterexample).
    - session stage spans (jaxmc/session.py): the `check` flow's
      existing `load` / `device_init` / `engine_build` / `search` /
      `search_fallback` phases are now emitted by CheckSession — same
      names, same meaning, whether the CLI or the serve daemon drives.
    - fused arm groups (backend/bfs.py): gauge `expand.fused_groups` — the
      number of fused expansion jits when a many-instance model splits
      per arm-group (JAXMC_FUSED_MAX_INSTANCES instances per group)
      instead of per action.

  (PR 8, still jaxmc.metrics/2 — all additive/optional; the mesh-
   resident multi-chip surface, backend/mesh.py:)
    - exchange strategy: gauges `mesh.exchange` ("a2a" | "gather"),
      `mesh.devices`; the strategy + gamma are also logged once per
      run.  Since PR 33 also `mesh.compact_form` ("runs" | "scatter":
      the merge's valid-candidate compaction, by the exchange's kind;
      resident loop only) and `mesh.finish_form` ("prefix" | "scatter":
      the explore-kept compaction, by whether the cfg has a
      CONSTRAINT).
    - resident-loop host traffic: counter `mesh.host_syncs` — one per
      level, counting the SINGLE replicated scalar-vector read the
      resident loop performs (on a clean run it EQUALS the level-record
      count: no row traffic crosses to the host between levels);
      counter `mesh.row_syncs` — whole-ring row pulls (violation trace
      assembly, checkpoints) — the only other device->host transfers.
    - exchange volume: counter `mesh.exchange_bytes` — whole-mesh bytes
      moved by the level exchanges (a2a: D^2*(B+SB)*(K+PW+1)*4 per
      level incl. the spill pass; gather: D^2*C*(K+PW)*4), computed
      from the static shapes.
    - a2a routing: gauges `mesh.a2a_gamma` (final bucket capacity
      factor; grows to the observed per-peer need on overflow),
      `mesh.a2a_spill` (total rows drained through the second
      all_to_all spill pass instead of rerunning the level),
      `mesh.a2a_max_bucket` (peak per-destination bucket occupancy).
    - shard health: gauge `mesh.shard_balance` — max/mean seen-shard
      occupancy (1.0 = perfectly balanced hash partition).
    - mesh level records add `devices`, `fc` (frontier capacity),
      `spill`, `max_bucket`, and the existing `fresh_compile` flag
      (so `window_recompiles` computes for mesh runs exactly like
      serve jobs).

  (PR 9, still jaxmc.metrics/2 — all additive/optional; the static-
   analysis surface, jaxmc/analyze/*:)
    - session stage span `analyze` (attrs: mode) between `load` and
      `engine_build` when `check --analyze != off`; engine-side spans
      `analyze_bounds` (the interval fixpoint) and `analyze_arms` (the
      per-arm demotion scan) inside the jax engine build.
    - bounds inference: gauge `analyze.proven_lanes` — int lanes whose
      packed width is a STATICALLY PROVEN interval (no sampling
      margin; the runtime OV_PACK check remains as a soundness net) —
      disjoint from `layout.pack_guarded_lanes`, which now counts ONLY
      observed-range lanes; gauge `analyze.bounds_converged` (bool).
      `obs report` renders the proven/(proven+guarded) ratio as a
      highlight line.
    - demotion prediction: counter `analyze.predicted_demotions` and
      gauge `analyze.arm_verdicts` ({arm label -> predicted reason});
      a predicted arm's reason string is IDENTICAL to the build-time
      demotion wording (kernel2's shared message constants), so the
      per-arm demotion table reads the same on either path.
    - linter: counter `analyze.lint_diags`, gauges
      `analyze.lint_max_severity` ("error"|"warning"|"info") and
      `analyze.lint_codes` (sorted JMC* code list).  Serve adds
      counter `serve.jobs_rejected` + trace event `serve.job_rejected
      {spec, codes}` for submissions refused by the submit-time lint
      gate.

  (PR 10, still jaxmc.metrics/2 — all additive/optional; the mesh
   rank-merge + superstep surface, backend/mesh.py:)
    - the mesh engine re-stamps `dedup.mode` at run start (the PR-6
      gauge was stamped before the mesh subclass forced fp128 keys,
      so mesh artifacts carried a stale value).  The shard-local
      merge is bfs._rank_merge and nothing else (PR 28): no gauge
      names it.
    - supersteps: `mesh.host_syncs` now counts SUPERSTEPS — one
      scalar-RING read per dispatch, each dispatch fusing up to
      JAXMC_MESH_SUPERSTEP levels in a device-side lax.while_loop —
      so host_syncs <= level-record count and < on any multi-level
      run; gauges `mesh.supersteps` (== host_syncs for the run) and
      `mesh.superstep_levels` (deepest fused dispatch).  Mesh level
      records gain `superstep` (which dispatch the level rode) and
      their `wall_s` is the dispatch wall amortized over its levels.
    - serve warm-registry eviction (ROADMAP item 3): counter
      `serve.evictions` + trace event `serve.evicted {sig}` when the
      bounded LRU (JAXMC_SERVE_WARM_MAX, default 32) drops the
      least-recently-used idle session; evicted signatures fall back
      to the final-checkpoint resume path (`serve.ckpt_resumes`).
    - mesh capacity profiles (compile/cache.py variant
      mesh-d<D>-<exchange>) gain the MSL key — the superstep
      controller's learned levels-per-dispatch — alongside
      SC/FC/TRL/GAM16.

  (PR 12, still jaxmc.metrics/2 — all additive/optional; the
   out-of-core hierarchical seen set, backend/tiers.py + ISSUE 12:)
    - seen-key mode: gauge `seen.mode` ("exact" | "fingerprint") — the
      dedup-key mode that actually ran (--seen forces it; auto keeps
      the width-based default); gauge `fingerprint.collision_p` — the
      reported n^2 * 2^-129 bound over every admitted key (device +
      cold tiers).  `result` gains `seen_mode` and (fingerprint runs)
      `collision_p`.
    - tier hierarchy: gauge `tier.occupancy` ({device, host, disk}
      keys), gauge `tier.probe_wall_s` (cumulative cold-probe wall),
      gauge `tier.device_cap` (the configured device cap, rows),
      counters `tier.spills` / `tier.spilled_keys` /
      `tier.compactions`; phase span `tier.spill {keys, bytes[,
      shards]}` per device-prefix spill; `result.tiers` carries the
      final stats() summary {host_keys, disk_keys, host_runs,
      disk_runs, spills, compactions, probe_wall_s[, io_degraded]
      [, cap_breached]}.  Since ISSUE 32 (bench/SPANS.ooc.md), per
      probed level the spans `tier.pull {rows}`, `tier.keys {rows}`,
      `tier.probe {keys, runs}`, `tier.push {rows}`; counters
      `tier.keys_probed` / `tier.keys_dropped` / `tier.redone_rows`
      (candidates of levels a spill rolled back and ran again; since
      ISSUE 50 also `tier.keys_verified`, below);
      gauge `tier.cap_breached` (rows a table grew to past a cap
      that could not seat one level's candidates); `tier.occupancy`
      is published at the end of every search of a capped engine
      (host and disk 0 where it never spilled: cold tiers last ONE
      search).
    - tier fault containment: trace event + gauge `tier.io_degraded
      {error}` when a disk-tier write fails (ENOSPC, the
      tier_io_error fault site) and the store degrades to
      host-tier-only — counts stay exact; `obs diff` treats its
      appearance like `device.demoted` (a named degradation).
    - truncation attribution: gauge `truncation.reason` and
      `result.trunc_reason` — the EXHAUSTED resource by name
      ("max_states: distinct N >= limit M", "drain", a tier/cap with
      the observed need) so capacity regressions are attributable;
      a bare `truncated` flag no longer ships alone.
    - capacity profiles: resident runs that spilled persist the
      optional TIERK key (cold-tier key total, pow2) alongside
      SC/FCap/AccCap/VC; a capped run that loads one stamps gauge
      `tier.predicted_keys` (the expected out-of-core magnitude)
      before the first spill.

  (PR 13, still jaxmc.metrics/2 — all additive/optional; cross-model
   vmapped batching, backend/batch.py + serve fleet wiring + ISSUE 13:)
    - batch scheduling (fleet telemetry): gauge `serve.batch_sigs`
      (distinct layout-compat classes seen this life), gauge
      `serve.batch_occupancy` (member width of the last vmapped
      cohort), gauge `serve.batch_compiles` (engine builds per cohort
      — 1 by construction), counters `serve.vbatch_jobs` /
      `serve.fastlane_jobs` (analyze-cost-routed queue jumps) /
      `serve.batch_incompatible` (parse-time-compatible cohorts the
      build refused; members requeued solo) / `serve.owner_respawns`
      + trace event `serve.owner_died {error}` (device-owner process
      death; jobs requeued, never lost).
    - batch engine (run-scope telemetry): gauge `batch.width` (member
      lanes in the last vmapped dispatch), counter `batch.dispatches`,
      gauges `batch.members` / `batch.occupancy` /
      `batch.dispatch_count` / `batch.lifted_consts` (the CONSTANT
      names riding the batch axis) / `batch.plan` (the shared
      pack-plan descriptor: width/packed_width/bits_per_state/...).
    - serve job artifacts: the `serve` block gains optional `bsig`
      (the layout-compat class), `cost_estimate` (analyze's
      state-space estimate consumed by the fast lane — null when the
      fixpoint bailed), `batch_occupancy`, `batch_dispatches`,
      `lifted_consts`, and `device_owner` (job ran in the owner
      process); job records carry `bsig`/`cost_estimate`/`fast_lane`.

  (PR 15, still jaxmc.metrics/2 — all additive/optional;
   independence-driven hot path, ISSUE 15:)
    - independence analysis: gauge `analyze.independence_pairs`
      (commuting arm pairs proven by the element-atom footprints),
      gauge `analyze.independence_safe` (arms eligible as singleton
      ample sets), gauge `expand.regrouped` (1 when the fused-group
      plan departed from the legacy contiguous one — counts/traces
      stay byte-identical; `expand.fused_groups` /
      `mesh.grouped_expand` may SHRINK under the new plan).
    - partial-order reduction (opt-in --por): gauge `por.enabled`
      (false + gauge `por.disabled_reason` when the model's
      constructs refuse the reduction), counters `por.ample_states` /
      `por.full_states` (states expanded through a singleton ample
      set vs fully), gauge `por.ample_ratio` (ample / total expanded),
      gauge `por.reduced_states` (the REDUCED run's distinct count —
      compare against an unreduced baseline's result.distinct; raw
      counts shrink BY DESIGN under --por), gauge `por.engine`
      ("interp" on the exact interpreter; "device" since PR 18, when
      the ample mask runs inside the fused device step — the PR 15
      demotion of device --por requests to the interpreter is gone).
    - bounds-sized engines: `profile.status` gains the value
      "predicted" (capacity ladder rung below `learned`: no saved
      profile, but a converged bounds fixpoint proved a state-count
      ceiling), gauges `profile.predicted_states` (the proven
      ceiling) and `profile.predicted_caps` (the buckets sized from
      it — a cold run then pays zero growth-retry recompiles).

  jaxmc.metrics/3  (PR 16) adds, all optional — the fleet-wide
   distributed-tracing + live-exposition surface; every /2 artifact
   remains valid (readers accept both):
    - trace-context propagation (obs/context.py): every trace event
      carries `tid` (16-hex fleet-wide trace id); every trace FILE
      opens with a `proc_meta` header {t, mono, pid, argv, psid,
      parent_span, env} — `psid` is this process's span id,
      `parent_span` the span of whoever spawned it (inherited over
      the JAXMC_TRACE_CTX env var as "<trace_id>:<parent_span_id>";
      absent -> this process is a trace root and `parent_span` is
      null).  Fork-pool workers (engine/parallel.py) write no files;
      the parent emits one `parallel.worker_span {pid, span, parent,
      level}` event per worker instead.  `python -m jaxmc.obs
      timeline` reconstructs the process tree from exactly these two
      shapes and flags orphan spans (a `parent_span` resolving to no
      known `psid`/worker span — a broken propagation hop).
    - search-progress estimation (obs/progress.py): trace event
      `progress_estimate {estimate, source}` when analyze's bounds
      fixpoint proved a state-space ceiling; gauge
      `search.progress_est` (fraction of the estimate explored, live
      during the run); heartbeat events gain `progress_fraction` /
      `progress_eta_s` / `progress_verdict` ("est" | "unbounded" —
      unbounded when no estimate exists or the observed distinct
      count exceeded it); `--progress-every` stdout lines (and their
      `log` mirrors) gain the same "~N% of est. M states, ETA Ks"
      suffix, including the immediate first line.
    - live exposition (serve/daemon.py): `GET /metrics` renders the
      daemon's counters/gauges plus per-job series in Prometheus
      text format 0.0.4.  Name grammar: `jaxmc_` + the internal
      dotted name with every character outside [a-zA-Z0-9_] mapped
      to `_` (obs.prom_name — e.g. `serve.queue_depth` ->
      `jaxmc_serve_queue_depth`, `search.progress_est` ->
      `jaxmc_search_progress_est`); per-job samples carry a
      `{job="<id>"}` label; derived per-job series:
      `jaxmc_job_running`, `jaxmc_job_levels`,
      `jaxmc_job_states_per_sec`, `jaxmc_job_progress_distinct`,
      `jaxmc_job_progress_eta_s`.  `GET /jobs/<id>/events` serves
      the job's bounded in-memory event ring (JAXMC_TRACE_RING,
      default 256 events) readable MID-RUN; `GET /status` gains a
      `progress` block {job id -> progress snapshot}.  Scrapes never
      block job threads (bounded ring + lock-copy snapshots).
    - per-job watchdogs (serve fleet): each in-daemon job and each
      owner-side solo job runs its own obs.Watchdog over the job's
      Telemetry, so one slow tenant cannot mask another job's stall;
      job heartbeat/stall events land in the per-job trace
      (`<spool>/results/<id>.trace.jsonl`) and ring.

  jaxmc.metrics/4  (PR 17) adds, all optional — the device profiler +
   HBM accounting + run-ledger surface; every /3 artifact remains
   valid (readers accept both):
    - the `prof{}` block (obs/prof.py): stamped by any run whose
      profiler recorded something (always under --profile; under the
      always-on cheap mode only when a dispatch site fired).  Grammar:
        prof: {
          mode: "cheap" | "wall" | "xla",
          sites: { <site>: {              # e.g. "bfs.resident_run",
            dispatches: int,              #   "mesh.superstep",
            recompiles: int,              #   "batch.vstep", ...
            wall_s?: float,               # block-until-ready wall
            arg_bytes?: int,              # cumulative argument bytes
            res_bytes?: int               # cumulative result bytes
          }, ... },
          hbm?: {peak_bytes: int},        # MEASURED: sum of device
                                          # memory_stats() peak_bytes_
                                          # in_use; absent where the
                                          # backend reports none
          xla_trace_dir?: str             # --profile=xla capture dir
        }
      Cheap and xla modes record counts/recompiles only; wall adds
      the sync + byte surfaces.  Profiling NEVER changes results:
      counts and traces stay bit-identical profile-on vs profile-off
      (pinned by tests/test_prof.py).  Gauge `compile.by_fun`
      {program: [compiles, seconds]} splits `compile.xla_compile_s`.
    - watchdog heartbeat events gain optional `device_mem_bytes` (the
      PROCESS's measured device peak) next to `rss_bytes`; stall events
      gain an optional dominant-site suffix in `msg` ("; 92% in
      mesh.superstep") naming where the wall concentrated at stall
      time.
    - live exposition (serve/daemon.py): per-job `/metrics` series
      gain `jaxmc_prof_site_dispatches` / `jaxmc_prof_site_wall_s`
      (labels `{job,site}`) and `jaxmc_hbm_peak_bytes` {job}.
      Completed jobs' `{job=...}` series persist for
      JAXMC_METRICS_JOB_TTL seconds (default 600) after completion —
      `jaxmc_job_running 0` plus the final gauges — then drop, so
      fleet lifetime no longer grows scrape cardinality without
      bound.
    - the run ledger (obs/ledger.py) is a SIBLING artifact, not part
      of the metrics schema: an append-only JSONL (default
      ~/.cache/jaxmc/ledger.jsonl; JAXMC_LEDGER=path overrides,
      =off disables) of one-line trajectory points
        {v:1, id, ts, rung, run, kind, states_per_sec, platform,
         env, source, sig?}
      content-addressed by `id` = sha1(rung, ts, rate, sig, env,
      source)[:16] — flock-appended, torn-line tolerant, idempotent
      to re-import.  `python -m jaxmc.obs history` renders/gates it.

  (PR 18, still jaxmc.metrics/4 — all additive/optional; device-side
   POR + dynamic element keys + structural batch-bound merge:)
    - device POR (--por on the jax/mesh backends): gauge `por.engine`
      gains the value "device" (ample mask applied INSIDE the fused
      step — level, resident, host_seen, and mesh supersteps; zero
      extra dispatches), gauge `por.device_masked_arms` (candidate
      rows the device mask dropped before dedup/exchange — the raw
      arm-level reduction the por.ample_states/full_states counters
      summarise per state), and the existing `por.ample_ratio` /
      `por.reduced_states` gauges are now also emitted by the device
      engines with IDENTICAL semantics (counts are bit-identical
      across engine shapes, including mesh data-parallel runs, where
      the ample probe is psum-distributed over the pre-level seen
      snapshot).  `por.disabled_reason` gains the mesh host-loop
      refusal (JAXMC_MESH_RESIDENT=0 escape hatch).
    - independence analysis: the arm-footprint report adds per-arm
      dynamic-key classes (element-commuting / whole-var writes /
      full-footprint bail) surfaced by `jaxmc info --cfg`; no new
      metrics keys.
    - batch engine: `batch.plan` (the shared pack-plan descriptor)
      now reflects the STRUCTURAL per-element bound merge — the donor
      packs container elements at the interval-union of every
      member's proven element bounds instead of falling back to
      whole-variable summaries; `bits_per_state` never exceeds the
      worst solo member's.

  (PR 19, still jaxmc.metrics/4 — all additive/optional; fleet-grade
   serving: leases + takeover, admission control, quarantine:)
    - serve fleet gauges: `serve.fleet_daemons` (live daemon-registry
      records within the lease TTL), `serve.leases_held` (jobs this
      daemon currently holds a lease on).
    - serve fleet counters: `serve.takeovers` (expired leases this
      daemon stole), `serve.jobs_adopted` (spool jobs pulled into the
      local queue by the fleet scanner), `serve.jobs_deferred`
      (submissions accepted but left unclaimed for a warmer peer),
      `serve.affinity_adoptions` (adoptions won on sig/bsig warmth),
      `serve.lease_lost` / `serve.lease_lost_drops` (renewals lost to
      a thief / results discarded because the lease was lost),
      `serve.lease_stalls` (injected fleet-tick stalls),
      `serve.quarantined` (jobs moved to spool/quarantine after the
      cross-daemon retry budget), `serve.admission_rejected` (429s),
      `serve.spool_retries` / `serve.spool_degraded` (transient spool
      write retries / writes that exhausted them).  `obs diff` flags
      the APPEARANCE of admission_rejected and spool_degraded like
      the tier degradation gauge (REGRESS lines).
    - job records (serve artifacts / GET /jobs): optional `daemon`
      (the fleet member that ran the job), `tenant` (admission
      accounting principal), `stolen_by` + `requeue_note` (lease-
      expiry takeover provenance); job status gains "quarantined".
    - batch counters: `batch.resume_refused` (a cohort member's
      checkpoint could not seed the merged layout; it ran fresh).

  (PR 21, still jaxmc.metrics/4 — additive; the chip bring-up surface:)
    - the device is NAMED everywhere: meta block `env` gains
      `device_kind`, `jaxlib_version`, `libtpu_version` beside
      {jax_version, platform, device_count}; gauge `device.kind`
      beside `device.platform` / `device.count`.  The three device
      fields are null until THIS process has a live jax backend
      (obs.live_devices — telemetry never initializes one);
    - `result.finished_on`: the engine that produced the result —
      the requested backend, or "interp" when the run demoted
      (session.demote_to_cpu, which now fires only with a host
      snapshot to resume);
    - counters `compile.xla_compiles` / `compile.xla_compile_s`: every
      XLA backend compile request this process made and the seconds
      they took (a persistent-cache hit counts its retrieval time) —
      set-up cost, never a metric of record;
    - gauge `mesh.device_peak_bytes` (MeshExplorer._mk): per-device
      `memory_stats()["peak_bytes_in_use"]`, where the backend
      reports it.

  (PR 34, still jaxmc.metrics/4 — additive, optional; records by
   IDENTITY beside the sums by name; bench/SPANS.records.md has the
   table of what each is taken from and which metric reads it:)
    - `prof.programs`: one record per executable, in first-dispatch
      order, made where a dispatch site's `_cache_size()` grew:
        {site, key, origin: "compiled" | "loaded", xla_s, dispatches,
         argument_bytes?, output_bytes?, alias_bytes?, temp_bytes?,
         hbm_bytes?}
      `key` is the engine's own cache key (the capacities); `origin`
      says whether jax's persistent cache answered for THAT call;
      `xla_s` the rise of `compile.xla_compile_s` around it; the byte
      fields are `memory_analysis()` of the executable the call made,
      per device, read where jax keeps it (never a second compile),
      absent where jax keeps none; hbm_bytes = argument + output -
      alias + temp.  `python -m jaxmc.obs top` prints the table.
      `origin` / `xla_s` are rises of process-wide counters (one
      compiling thread at a time), `dispatches` goes to the newest
      executable of the jitted function called (obs/prof.py).
      `prof.sites[*].launch_s`: host seconds inside the site's calls
      up to their return (the `launch` column of `obs top`).
      A THIRD origin, "held" (ISSUE 37): the engine's own cache
      missed and its PROCESS held the program already (compile/
      cache.py's registry, same program signature): an earlier
      engine's jitted callable is dispatched, no executable is made
      and no site's `_cache_size()` grows, so the record is a copy of
      the one that executable got when it came into being (site, key,
      the byte fields) with origin "held", `xla_s` 0.0 and this
      recorder's own `dispatches`; the gauges below are published as
      for a new executable.  Counters `compile.program_hits` /
      `compile.program_misses` (asks of the registry that found /
      made the program) and `compile.program_unkeyed` (the engine has
      no program signature: hybrid, or something in its model that
      does not render canonically — it keeps its own jits); a hit
      leaves `compile.xla_compile_s` in the counters, 0.0 where
      nothing else compiled or loaded; float counter
      `compile.program_sig_s`, the host seconds the signature took.
      `serve.program_hits` in a served job's `serve` block is
      `compile.program_hits` of the job's recorder (serve/protocol.py);
      `python -m jaxmc.obs report` prints a `programs:` line.
    - gauges `program.temp_bytes` / `program.hbm_bytes`: those fields
      of the program with the largest hbm_bytes dispatched so far
      (argument_bytes stays in the record, where `top` prints it).
    - float counters, seconds on `time.perf_counter` taken where the
      work happens (the form of `compile.xla_compile_s`; NOT spans,
      so they take no idle seconds out of `search.seed`):
      `seed.keys_s` (init keys, owner hash, lexsorts), `seed.tables_s`
      (what the host builds of the tables: since ISSUE 35 their
      HEADS, the init or checkpoint rows — the capacity-sized tables
      are filled on the device), `seed.upload_s` (the calls that hand
      the heads to the device and make its tables, up to their
      return), `dispatch.launch_s` (every dispatch site's `fn(*args)`
      up to its return).
    - top-level `requests`: the last 512 search records, one per
      closed `search` span of `CheckSession.explore()`:
        {rid, name, t0, wall_s, cpu_s, spans: {name: wall_s},
         counters: {the four above: rise}, dispatches,
         origins: {"compiled" | "loaded" | "held": dispatches}}
      `cpu_s` is `time.process_time()`: a search whose wall rose and
      whose CPU seconds did not was not running.  `rid` counts the
      recorder's searches from 1.  The summary is the records' only
      sink: the trace stream carries no copy and its span events no
      `rid` (nothing reads a stream by search).  `python -m jaxmc.obs
      report` prints the searches' median and largest wall and the
      piece that grew in the slowest.

  (PR 39, still jaxmc.metrics/4 — all additive/optional; a vmapped
   cohort's wall accounted for, backend/batch.py + the host_seen loop
   of backend/bfs.py, ISSUE 39:)
    - the COHORT's recorder is its leader's (serve/owner.py
      `run_vbatch` hands `BatchCheckEngine` the first member's): the
      one build, the supersteps and the vmapped program's record reach
      the client in the leader's artifact (up to PR 38 they went to
      the process's recorder and to whichever member's thread fired).
      Spans `batch.build` (all of `build()`: every member's `load`
      in ITS artifact, `batch_sample`, `engine_build` and the donor's
      own build spans beside it) and `batch.run` (all of `run()`);
      per superstep `batch.dispatch` (upload, the vmapped program,
      its result block fetched: a synchronous round trip under the
      dispatcher's lock; the span a trace's dispatches are counted by,
      `jaxmc.batch.dispatch`).
    - float counters in the leader's artifact, seconds on
      `time.perf_counter` (no event a dispatch: a span there costs
      ~24 us with a job's trace file open): `batch.stack_s` (the
      pending chunks stacked into one [B, CH, PW] block on the host),
      `batch.unstack_s` (each member handed its slice),
      `batch.upload_s` and `batch.fetch_s` (the
      round trip's two ends, round the site's own launch seconds:
      prof site `batch.vstep`), `batch.first_dispatch_s` (the
      cohort's FIRST call alone: `jax.jit(jax.vmap(core))` is made
      anew for every cohort, so it traces, lowers and compiles or
      loads); counters `batch.dispatches` and `batch.lane_steps` (the
      sum of the dispatches' widths: lane_steps / (members x
      dispatches) is the share of member lanes that held a chunk).
      The program record of `batch.vstep` (`prof.programs`, gauges
      `program.temp_bytes` / `.hbm_bytes`) lands there too.
    - (PR 40) beside `batch.fetch_s`: counter `batch.fetch_transfers`
      (device-to-host transfers: ONE a superstep, so it equals
      `batch.dispatches`; up to PR 39 the nine outputs came one by
      one, nine transfers) and float counter `batch.fetch_mb` (bytes
      brought back / 10^6).  The vmapped program packs a member's nine
      outputs into one int32 block [PW + K + 1, C + 128], words-major
      (candidate words, key lanes, one flags word a slot; `gen` and
      `overflow` in the 128 header columns), and a superstep of fewer
      than B live lanes gathers those lanes on the device first: prof
      site `batch.take`, one small program per width, shared by every
      cohort of a process (its record lands in the artifact of the
      cohort whose call made the executable).
    - every MEMBER's artifact: float counter `batch.barrier_wait_s`
      (its seconds inside supersteps that were not the firing
      itself: the lock, the slower members' chunks, the dispatch
      another thread ran) and the host_seen loop's own, published at
      the end of every level, a solo `--host-seen` run's too (a level
      that ends the search early keeps its own to itself):
      `hostseen.chunks`, `hostseen.step_s` (inside the step's call:
      solo, the asynchronous enqueue; in a cohort the whole
      superstep), `hostseen.store_s` / `hostseen.store_keys` (the
      native fingerprint store's `insert`, the key columns' copy
      included, and `contains` under POR; keys handed to it),
      `hostseen.book_s` (the rest of the chunk loop: outputs forced
      and fetched, `np.nonzero`, the new rows' takes, provenance) and
      `hostseen.tail_s` (from the chunk loop's end to the level's:
      concatenation, the next frontier, the level record).
    - serve job artifacts and records: a cohort's `serve` block and
      its members' records say `device_owner` as a solo job's do
      (`daemon._run_vbatch`; up to PR 38 a cohort's said nothing).
    - `python -m jaxmc.obs report` prints a `cohort:` line (leader)
      and a `host_seen:` line (any member, any `--host-seen` run).

  (PR 44, still jaxmc.metrics/4 — all additive/optional; counterexample
   traces on the resident engine, backend/bfs.py, ISSUE 44.  A resident
   search that keeps no trace (`--no-trace`) emits NONE of these:)
    - spans `search.trace {depth}` (all of a violating search's
      reconstruction) and inside it `trace.walk` (the ONE dispatch of
      the backward walk and the one fetch of its result block) and
      `trace.decode` (rows to states and labels).  Device scopes
      `jaxmc.trace.log` (each level's new frontier rows appended to
      the state log, inside the resident loop) and `jaxmc.trace.walk`
      (the logged levels expanded again, successors packed and
      compared with the target; its expansion carries this scope, not
      `jaxmc.expand`).  Prof site `bfs.trace_walk`, keyed (log rows,
      walk chunk, levels bucket); the traced search program is site
      `bfs.resident_run` with (LogCap, levels a dispatch) ending its key.
    - counters `search.log_rows` / `search.log_bytes` (rows the search
      appended to its log — the initial frontier and every level it
      went on from — and rows x PW x 4), `search.trace_rows_expanded`
      (log rows of the chunks the walk visited: a level stops at the
      first chunk that holds a parent) and `search.trace_len` (states
      of the behaviour returned).  `search.table_bytes` counts the
      log's table ((LogCap + FCap) x PW x 4) and `search.seed_bytes`
      the initial frontier a second time, where a log is kept.

  (PR 47, still jaxmc.metrics/4 — all additive/optional; SYMMETRY over a
   real group, compile/symmetry2.py, ISSUE 47.  A cfg WITHOUT a SYMMETRY
   line emits NONE of these, and its lowered programs are byte-identical
   to what they were:)
    - gauges `symmetry.form` — which canonicaliser the device runs:
      "sorted" (the group is a product of full symmetric groups whose
      members are mere positions of the layout: a sorting network over
      the per-member sub-vectors), "unrolled" (one row transform per
      element of the closed group, at most JAXMC_SYM_GROUP_LIMIT of
      them) or "none" (identity group, or the unreduced fallback) — and
      `symmetry.group_order` (elements of the group the device reduces
      by, the identity included; 1 where the form is "none").  The
      three-way disclosure above says the form too:
      `sym=device-reduced (sorted)`.  The form is part of
      `TpuExplorer._program_sig`.
    - device scope `jaxmc.canon`, INSIDE `jaxmc.keys`, around the
      canonicaliser on every engine (the level step, the resident loop,
      the mesh, `bfs.host_keys`): a trace reduction that takes the
      innermost `jaxmc.*` component (bench/spans.py) books its
      operations apart from pack and fingerprint.
    - counter `search.canon_rows` — rows the canonicaliser took in a
      search: every generated state once (the successors on the device,
      the initial states on the host's side of the same function), so it
      equals the search's `generated`.
    - `generated` itself counts EVERY initial state, as TLC does, also
      one whose SYMMETRY orbit or VIEW value an earlier one already
      stored (until PR 47 only the first of each was counted, on every
      engine and in the interpreter alike); `distinct` is unchanged.

  (PR 49, still jaxmc.metrics/4 — all additive/optional; a served job's
   stations, serve/daemon.py + serve/owner.py + serve/queue.py,
   ISSUE 49.  Nothing of it exists outside `jaxmc.serve` but the
   checkpoint's bytes:)
    - the `serve` block of a served job's artifact gains `stations`
      (wall-clock marks `submitted_at`, `enqueued_at`, `claimed_at`,
      `owner_spawned_at`?, `owner_sent_at`, `owner_began_at`,
      `owner_ended_at`, `owner_received_at`, `finished_at`: the same
      numbers the job's record carries; absent where the job passed
      none) and the seconds `owner_wait_s`, `owner_envelope_s`,
      `publish_s`, `owner_spawn_s`? (serve/protocol.py has the table);
      `python -m jaxmc.obs report` prints one `stations:` line.
    - daemon recorder: the `job` / `vbatch` span of an owner-run job
      has two children, `job.owner_wait` (the worker wants
      `DeviceOwner._lock` -> it holds it and a live owner) and
      `job.owner_run` (the request into the pipe -> its answer out).
    - owner process: ONE envelope span a job on the job's own recorder
      — `job` (`run_solo`) or, on the leader's, `vbatch` {members}
      (`run_vbatch`) — opened as the recorder is made and closed before
      the summary: in the owner's device trace (`jaxmc.job`,
      `jaxmc.vbatch`) a device-idle piece is under a job or under none.
    - span `checkpoint.write` gains the attribute `bytes` and every
      recorder the counter `checkpoint.bytes`: the size of each file
      the span wrote, after the rename (engine/ckpt.py
      `write_periodic`, `bfs._write_ck`).

  (PR 50, still jaxmc.metrics/4 — all additive/optional; the cold probe
   meets a host run at its fence, backend/tiers.py, ISSUE 50.  Only a
   capped search that probed a cold run emits it:)
    - counter `tier.keys_verified`, beside `tier.keys_probed` /
      `tier.keys_dropped` (bfs._tier_probe): the queries that went on
      to a run's deciding whole-row compare, summed over the runs a
      probe searched.  A HOST run is met at its fence first (its
      keys' leading 8 bytes as a sorted native column,
      tiers._lead_column, searched natively) and passes on only the
      queries whose leading bytes it holds, so there the count is the
      hits plus the keys that share 8 leading bytes with a stranger
      (with 128-bit fingerprints: the hits); a DISK run has no fence
      and every query counts.  Always >= `tier.keys_dropped` and <=
      `tier.keys_probed` x runs.  `result.tiers` (stats()) gains
      `keys_verified`, the search's total.  The span `tier.probe
      {keys, runs}` covers what it covered: all of `TieredSeen.probe`
      — the queries' leading bytes, every host run's fence and
      whole-row search, every disk run's whole-row search.

  (PR 51, still jaxmc.metrics/4 — all additive/optional; a cfg's
   CONSTRAINT at size, ISSUE 51.  A cfg WITHOUT a CONSTRAINT emits NONE
   of these, and its lowered programs are byte-identical to what they
   were:)
    - gauge `constraint.compiled` — the CONSTRAINTs compiled for the
      device (beside `expand.constraints_interp`, those the interpreter
      judges on the host: an engine with any of those is hybrid).
    - device scope `jaxmc.constraint` — on the resident engine round
      the unpack of the level's new rows, the predicates over them, the
      stable sort that names the kept rows first and the kept rows'
      gather into the frontier (the FIRST gather of the new rows stays
      under `jaxmc.compact`; up to PR 50 the predicates read
      `jaxmc.scan` and the sort and second gather `jaxmc.compact`); on
      the level engine and the mesh round the unpack and the predicates.
    - counter `search.rows_discarded` — rows that entered the seen table
      in a search and that a CONSTRAINT kept out of the frontier: TLC
      fingerprints a constraint-violating successor and then discards it
      (not distinct, not invariant-checked, not explored).  Host
      arithmetic on what every dispatch already reports: the rise of the
      table's occupancy less the rise of `distinct`.  With it,
      `search.rows_new` + `search.rows_discarded` is what the seen table
      gained (the initial states apart); under `--seen-cap` a state that
      was spilled, met again and discarded again counts again.
    - counter `search.slots_constrained` — the slots the predicates (and
      on the resident engine the sort) ran over: levels run x AccCap on
      the resident engine (a rolled-back level counted, as
      `search.slots_sorted` counts its own), the candidate block C a
      level on the level engine, levels x D x the merge's key slots on
      the mesh.
"""

from __future__ import annotations

from typing import Any, Dict

SCHEMA = "jaxmc.metrics/4"

# every schema revision an artifact may carry and a reader must accept
# (additive history: a v1 artifact simply lacks the v2 optional surface)
SCHEMAS = ("jaxmc.metrics/1", "jaxmc.metrics/2", "jaxmc.metrics/3",
           "jaxmc.metrics/4")

# top-level summary keys every artifact must carry
REQUIRED_KEYS = ("schema", "started_at", "wall_s", "phases", "counters",
                 "gauges", "levels")

# keys a `check` run's artifact adds
CHECK_KEYS = ("backend", "spec", "result")

# required fields of summary["result"] for a check run
RESULT_KEYS = ("ok", "distinct", "generated", "diameter", "truncated")

PHASE_KEYS = ("name", "wall_s", "count")

# required fields of the watchdog trace events (jaxmc/obs/watchdog.py)
HEARTBEAT_KEYS = ("ev", "t", "wall_s", "open_spans", "last_level",
                  "progress_seq")
STALL_KEYS = ("ev", "t", "stalled_for_s", "threshold_s", "open_spans",
              "last_level")


def validate_summary(s: Dict[str, Any], check_run: bool = False) -> None:
    """Structural validation; raises ValueError naming the defect."""
    if not isinstance(s, dict):
        raise ValueError(f"summary is {type(s).__name__}, not a dict")
    missing = [k for k in REQUIRED_KEYS if k not in s]
    if check_run:
        missing += [k for k in CHECK_KEYS if k not in s]
    if missing:
        raise ValueError(f"summary missing keys: {missing}")
    if s["schema"] not in SCHEMAS:
        raise ValueError(f"schema {s['schema']!r} not in {SCHEMAS!r}")
    if not isinstance(s["phases"], list):
        raise ValueError("phases is not a list")
    for ph in s["phases"]:
        miss = [k for k in PHASE_KEYS if k not in ph]
        if miss:
            raise ValueError(f"phase {ph!r} missing {miss}")
        if ph["wall_s"] < 0:
            raise ValueError(f"phase {ph['name']} has negative wall_s")
    if not isinstance(s["counters"], dict) or \
            not isinstance(s["gauges"], dict):
        raise ValueError("counters/gauges must be dicts")
    if not isinstance(s["levels"], list):
        raise ValueError("levels is not a list")
    prev = None
    for rec in s["levels"]:
        if "level" not in rec:
            raise ValueError(f"level record {rec!r} missing 'level'")
        if prev is not None and rec["level"] < prev:
            raise ValueError(
                f"level indices not monotone: {rec['level']} after {prev}")
        prev = rec["level"]
    if check_run:
        res = s["result"]
        miss = [k for k in RESULT_KEYS if k not in res]
        if miss:
            raise ValueError(f"result missing keys: {miss}")


def validate_trace_event(e: Dict[str, Any]) -> None:
    """Structural validation of one trace JSONL event. Only the watchdog
    events carry enough required structure to pin; other event kinds
    need just the `ev`/`t` envelope."""
    if not isinstance(e, dict):
        raise ValueError(f"event is {type(e).__name__}, not a dict")
    if "ev" not in e:
        raise ValueError("event missing 'ev'")
    # every event is timestamped: `t` everywhere except span-close,
    # which carries its open time as `t0` (see the grammar above)
    tkey = "t0" if e["ev"] == "span" else "t"
    if tkey not in e:
        raise ValueError(f"event {e['ev']!r} missing {tkey!r}")
    if e["ev"] in ("span", "span_open"):
        for key in ("id", "parent_id"):  # optional, since PR 24
            if not isinstance(e.get(key), (int, type(None))):
                raise ValueError(f"{e['ev']}.{key} is not an int")
    required = {"heartbeat": HEARTBEAT_KEYS, "stall": STALL_KEYS}.get(
        e["ev"])
    if required is None:
        return
    miss = [k for k in required if k not in e]
    if miss:
        raise ValueError(f"{e['ev']} event missing {miss}")
    if not isinstance(e["open_spans"], list):
        raise ValueError(f"{e['ev']}.open_spans is not a list")
    if e["ev"] == "heartbeat" and e["wall_s"] < 0:
        raise ValueError("heartbeat has negative wall_s")
    if e["ev"] == "stall" and e["stalled_for_s"] < 0:
        raise ValueError("stall has negative stalled_for_s")
