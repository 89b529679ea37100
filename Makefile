# jaxmc build/check driver — mirrors the reference's Makefile contract
# (/root/reference/Makefile:1-7: all = transpile + test) with the checker
# backend selectable: BACKEND=interp (exact Python oracle) | jax (TPU path).

BACKEND   ?= interp
SPEC      ?= specs/transfer_scaled.tla
PY        ?= python3
REFERENCE ?= /root/reference

all: test

# model-check one spec (auto-discovers <spec>.cfg).
# BACKEND=tlc shells out to stock TLC — the reference's own `make test`
# driver (/root/reference/Makefile:6-7) and the 100x target's anchor —
# when a JVM provides it, and refuses with ONE clear line otherwise
# (BASELINE.md documents the full TLC measurement recipe).
check:
	@if [ "$(BACKEND)" = "tlc" ]; then \
	  if command -v tlc >/dev/null 2>&1; then \
	    tlc $(SPEC); \
	  else \
	    echo "BACKEND=tlc: no JVM/tlc on PATH; interp is the oracle here" \
	         "(see BASELINE.md 'Measuring TLC' for the recipe)" >&2; \
	    exit 2; \
	  fi; \
	else \
	  $(PY) -m jaxmc check $(SPEC) --backend $(BACKEND); \
	fi

# check every checkable spec+cfg with its EXPECTED verdict, the way the
# reference's `make test` runs `tlc *tla` (includes expected-violation
# models); SLOW=--slow adds the multi-minute ones
SLOW ?=
check-corpus:
	@if [ "$(BACKEND)" = "tlc" ]; then \
	  if ! command -v tlc >/dev/null 2>&1; then \
	    echo "BACKEND=tlc: no JVM/tlc on PATH; interp is the oracle here" \
	         "(see BASELINE.md 'Measuring TLC' for the recipe)" >&2; \
	    exit 2; \
	  elif [ ! -d $(REFERENCE) ]; then \
	    echo "BACKEND=tlc: reference corpus not mounted at $(REFERENCE)" \
	         "(set REFERENCE=<dir>); interp is the oracle here" >&2; \
	    exit 2; \
	  else \
	    cd $(REFERENCE) && tlc *tla; \
	  fi; \
	else \
	  $(PY) -m jaxmc sweep --backend $(BACKEND) $(SLOW); \
	fi

test:
	$(PY) -m pytest tests/ -q

# static analysis gates (ISSUE 9) — both run inside `make bench-check`:
#   lint-corpus  the TLA+ corpus linter over every manifest pair; the
#                repo-local pairs must be clean modulo explicit waivers
#                (corpus.py Case.lint_waive), the linttoy fixture must
#                produce every expected diagnostic class, and
#                reference-rooted pairs SKIP (parseably) when
#                /root/reference is absent
#   pylint       Python-side static analysis of jaxmc itself — ruff
#                (pyflakes+bugbear, see ruff.toml) when the host has
#                it, else the builtin checker in jaxmc/analyze/pylint.py
lint-corpus:
	$(PY) -m jaxmc.analyze lint-corpus

pylint:
	@if command -v ruff >/dev/null 2>&1; then \
	  ruff check jaxmc; \
	else \
	  $(PY) -m jaxmc.analyze pylint jaxmc; \
	fi

# fault-injection smoke suite (ISSUE 4): every chaos-marked test — the
# JAXMC_FAULTS harness killing pool workers, corrupting checkpoints,
# failing device init, SIGKILLing whole runs mid-level — on the CPU
# backend. The heavyweight kill/resume legs are additionally marked
# `slow`, so they run here but stay out of tier-1 timing.
chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m chaos

# one-shot TLC measurement of the bench model (BASELINE.md recipe): the
# literature-sourced 5000 st/s estimate becomes a MEASUREMENT wherever a
# JVM exists — divide TLC's reported generated total by wall seconds.
# The bench spec transitively EXTENDS
# the reference raft.tla, and plain tlc resolves modules from the cwd —
# so stage the shim + the reference module side by side first.
bench-tlc:
	@command -v tlc >/dev/null 2>&1 || { \
	  echo "bench-tlc: no JVM/tlc on PATH; interp is the oracle here" \
	       "(see BASELINE.md 'Measuring TLC')" >&2; exit 2; }
	@[ -f $(REFERENCE)/examples/raft.tla ] || { \
	  echo "bench-tlc: reference corpus not mounted at $(REFERENCE)" \
	       "(set REFERENCE=<dir>); the bench spec EXTENDS its raft.tla" \
	       >&2; exit 2; }
	rm -rf /tmp/jaxmc_tlc_bench && mkdir -p /tmp/jaxmc_tlc_bench
	cp specs/MCraftMicro.tla specs/MCraft.tla \
	    specs/MCraft_3s_bench.cfg /tmp/jaxmc_tlc_bench/
	cp $(REFERENCE)/examples/raft.tla /tmp/jaxmc_tlc_bench/
	cd /tmp/jaxmc_tlc_bench && time tlc -config MCraft_3s_bench.cfg \
	    MCraftMicro.tla

# resume (or start) the MCserializableSI_env exhaustive run with
# checkpointing — the open count-pin item: run until it
# completes, then pin the printed generated/distinct totals in
# jaxmc/corpus.py (the slow test test_si.py::test_si_env_exhaustive_pin
# enforces them from then on)
pin-si-env:
	$(PY) -m jaxmc check specs/MCserializableSI.tla \
	    --cfg specs/MCserializableSI_env.cfg -I $(REFERENCE)/examples \
	    --checkpoint ck_si_env.ck --checkpoint-every 120 \
	    $$( [ -f ck_si_env.ck ] && echo --resume ck_si_env.ck )

# perf-regression gate: run a short fixed-model exact-engine bench twice
# (one serial leg, one --workers 4 leg) and gate each leg LIKE-FOR-LIKE
# against the baseline artifact saved by the previous bench-check run
# (python -m jaxmc.obs diff --fail-on-regress: states/sec drop, backend
# demotion, phase blowups). First invocation snapshots the baselines;
# run it on main before a perf-sensitive change, then again after.
# `make bench-check-reset` discards the baselines.
BENCH_CHECK_SPEC ?= specs/transfer_scaled.tla
BENCH_CHECK_DIR  ?= /tmp
# repo-local kernel-vs-interp rungs (ISSUE 6): the three feature axes —
# plain wide search, cfg VIEW, cfg SYMMETRY — at bench scale
KERNELBENCH_RUNGS ?= specs/transfer_scaled.tla specs/viewtoy_scaled.tla \
                     specs/symtoy_scaled.tla
bench-check:
	JAX_PLATFORMS=cpu $(PY) -m jaxmc check $(BENCH_CHECK_SPEC) \
	    --workers 1 --max-states 20000 --quiet \
	    --metrics-out $(BENCH_CHECK_DIR)/jaxmc_bench_check_serial.json
	JAX_PLATFORMS=cpu $(PY) -m jaxmc check $(BENCH_CHECK_SPEC) \
	    --workers 4 --max-states 20000 --quiet \
	    --metrics-out $(BENCH_CHECK_DIR)/jaxmc_bench_check_par.json
	# warm-start leg (ISSUE 5): a resident truncation checkpoint, then a
	# steady-state resume — the compile-excluded window the bench's full
	# rung now measures, gated like-for-like against its saved baseline
	JAX_PLATFORMS=cpu $(PY) -m jaxmc check $(BENCH_CHECK_SPEC) \
	    --backend jax --platform cpu --resident --no-trace --quiet \
	    --max-states 4000 \
	    --checkpoint $(BENCH_CHECK_DIR)/jaxmc_bench_check_warm.ck
	JAX_PLATFORMS=cpu $(PY) -m jaxmc check $(BENCH_CHECK_SPEC) \
	    --backend jax --platform cpu --resident --no-trace --quiet \
	    --max-states 20000 \
	    --resume $(BENCH_CHECK_DIR)/jaxmc_bench_check_warm.ck \
	    --metrics-out $(BENCH_CHECK_DIR)/jaxmc_bench_check_warmleg.json
	@for leg in serial par warmleg; do \
	  cur=$(BENCH_CHECK_DIR)/jaxmc_bench_check_$$leg.json; \
	  base=$(BENCH_CHECK_DIR)/jaxmc_bench_check_$$leg.baseline.json; \
	  if [ -f $$base ]; then \
	    echo "== $$leg leg vs saved baseline =="; \
	    $(PY) -m jaxmc.obs diff --fail-on-regress --threshold 25 \
	        $$base $$cur || exit 1; \
	  else \
	    cp $$cur $$base; \
	    echo "$$leg baseline saved -> $$base"; \
	  fi; \
	done
	# kernel-vs-interp leg (ISSUE 6): on every repo-local rung the
	# cpu-XLA kernel (steady state: one warm-up excluded) must meet or
	# exceed the serial interpreter's states/sec, with bit-identical
	# counts; jaxmc.kernelbench writes the two artifacts and gates them
	# through `python -m jaxmc.obs diff --fail-on-regress` ([interp,
	# kernel] order — a slower kernel raises the REGRESS flag)
	@for spec in $(KERNELBENCH_RUNGS); do \
	  echo "== kernel-vs-interp leg: $$spec =="; \
	  JAX_PLATFORMS=cpu $(PY) -m jaxmc.kernelbench $$spec \
	      --out-dir $(BENCH_CHECK_DIR) || exit 1; \
	done
	# cross-model batching leg (ISSUE 13): a cold cohort of
	# layout-compatible jobs must run as ONE vmapped engine at >= 2x
	# the sequential cold throughput with bit-identical per-member
	# counts — see batch-check below
	$(MAKE) batch-check
	# checking-as-a-service leg (ISSUE 7): the warm second submission
	# to a live daemon must be a checkpoint-resume with ZERO in-window
	# recompiles — see serve-check below
	$(MAKE) serve-check
	# observability leg (ISSUE 16): live daemon scraped mid-run
	# (/metrics parses, per-job progress gauge moves), multi-process
	# timeline with zero orphan spans — see trace-check below
	$(MAKE) trace-check
	# fleet-serving leg (ISSUE 19): multi-daemon spool under SIGKILLs —
	# lease takeover with bit-identical resumed counts, warm-hit
	# routing beating round-robin, 429 + Retry-After under overload,
	# poison-job quarantine (parseable FLEET-CHECK SKIP on hosts that
	# cannot run a fleet) — see fleet-check below
	$(MAKE) fleet-check
	# multi-chip parity leg (ISSUE 8): D=2 and D=4 virtual-device mesh
	# runs must match the manifest pins bit-for-bit — see
	# multichip-check below
	$(MAKE) multichip-check
	# backend-portability leg (ISSUE 11): preflight oracle smoke +
	# per-live-platform baseline gate (SKIP lines for dead platforms)
	$(MAKE) backend-check
	# out-of-core leg (ISSUE 12): capped exhaustive run via tier spill
	# + fingerprint parity — see ooc-check below
	$(MAKE) ooc-check
	# independence/reduction leg (ISSUE 15): regroup parity, --por
	# verdict preservation + >=30% explored-state reduction, and the
	# predicted capacity rung's zero-growth cold run — see por-check
	$(MAKE) por-check
	# profiler/ledger leg (ISSUE 17): warm `--profile` runs must
	# attribute >= 90% of the search wall to named dispatch sites,
	# profile-on/off counts must be bit-identical, and the temp-ledger
	# regression gate must pass (and trip on a synthesized slowdown)
	# — see prof-check below
	$(MAKE) prof-check
	# static-analysis legs (ISSUE 9): an analyzer regression gates the
	# same way perf regressions do — the corpus must stay lint-clean
	# (modulo manifest waivers) and jaxmc's own Python must stay free
	# of dead imports/locals
	$(MAKE) lint-corpus
	$(MAKE) pylint

# multi-chip parity gate (ISSUE 8/10): the mesh-resident engine
# (owner-routed a2a dedup, seen shards + frontier + trace ring on
# device, scalars-only host reads, rank-merge + fused supersteps) at
# D=2 and D=4 VIRTUAL cpu devices on the repo-local bench rungs
# (+ MCraft_micro when the reference corpus is mounted — a parseable
# SKIP line otherwise).  Counts must equal the corpus manifest pins,
# host_syncs may never exceed the level count (supersteps make it
# smaller), and each leg's metrics artifact gates via
# `python -m jaxmc.obs diff --fail-on-regress` against a saved
# baseline (first run snapshots it; baselines live in
# $(BENCH_CHECK_DIR)/jaxmc_multichip_*.baseline.json).
# Finally, when two committed MULTICHIP_r* scaling artifacts exist,
# `obs diff` gates the newer per-rung states/sec/chip against the
# older (wired into `make bench-check` through this target).
MULTICHIP_DEVICES ?= 2,4
# every committed schema>=1 scaling artifact, ordered by recorded
# timestamp inside `obs diff` (ISSUE 17: diff expands globs itself,
# so new MULTICHIP_r* drops join the gate without a Makefile edit;
# r01-r05 predate the /1 schema and stay out of the pattern)
MULTICHIP_GLOB ?= MULTICHIP_r0[6-9].json
multichip-check:
	$(PY) -m jaxmc.meshbench check --devices $(MULTICHIP_DEVICES) \
	    --out-dir $(BENCH_CHECK_DIR)
	@if ls $(MULTICHIP_GLOB) >/dev/null 2>&1; then \
	  echo "== multichip scaling curve: $(MULTICHIP_GLOB) =="; \
	  $(PY) -m jaxmc.obs diff --fail-on-regress --threshold 25 \
	      '$(MULTICHIP_GLOB)' || exit 1; \
	fi

# backend-portability gate (ISSUE 11): two legs, both parseable —
#   1. oracle smoke: the preflight oracle (jaxmc/backend/oracle.py)
#      must find at least one live platform inside its deadline (<10s;
#      a device that hangs at init costs the deadline, never the run);
#   2. per-backend baseline: for every LIVE platform, one pinned
#      `--backend <plat>` check leg gated against that platform's OWN
#      saved baseline via `python -m jaxmc.obs diff --fail-on-regress`
#      (first run snapshots it — how a new platform's baseline is
#      seeded, BASELINE.md "Per-backend baselines").  Dead platforms
#      print `BACKEND-CHECK SKIP <plat>: <reason>` and never fail, so
#      the target is green on a cpu-only builder box and a TPU pod
#      alike; live platforms must agree on reachable-state counts.
backend-check:
	$(PY) -m jaxmc.backend.check --out-dir $(BENCH_CHECK_DIR)

# out-of-core seen-set gate (ISSUE 12): on the repo-local overflow
# fixture (specs/ooc_scaled.tla) — (1) uncapped exact run == manifest
# pins; (2) JAXMC_SEEN_CAP forces the device seen table to ~17% of the
# state count and a tiny host budget forces the disk tier: the run
# must complete EXHAUSTIVELY via hierarchical tier spill with
# bit-identical counts, gated via `python -m jaxmc.obs diff
# --fail-on-regress` against its saved baseline; (3) --seen
# fingerprint parity + the measured >=4x states-per-device-tier ratio
# (BASELINE.md "Out-of-core"); (4) capped-vs-uncapped violation
# traces byte-identical.  A jax-less container prints `OOC-CHECK
# SKIP ...` and exits 0.
ooc-check:
	JAX_PLATFORMS=cpu $(PY) -m jaxmc.oocbench \
	    --out-dir $(BENCH_CHECK_DIR)

# independence/reduction gate (ISSUE 15): (1) unreduced portoy_ok
# counts == manifest pins; (2) --por completes with >= 30% fewer
# explored distinct states and preserves the deadlock/invariant
# verdicts of the portoy rungs; (3) the grouped host_seen path with
# independence regrouping ON vs OFF stays byte-identical (trace
# compared line-for-line, artifact gated via `python -m jaxmc.obs
# diff --fail-on-regress` against its saved baseline); (4) a COLD
# resident run of the fully-proven fixture takes the `predicted`
# capacity rung and pays zero growth recompiles.  A jax-less
# container still runs the interpreter legs and prints `POR-CHECK
# SKIP ...` for the rest.
por-check:
	JAX_PLATFORMS=cpu $(PY) -m jaxmc.porbench \
	    --out-dir $(BENCH_CHECK_DIR)

# the published scaling curve (ISSUE 8/10): per-rung, per-D warm-up +
# timed fully-warm mesh runs over D in {1,2,4,8} virtual devices
# (real chips when JAXMC_MESHBENCH_PLATFORM names an accelerator) —
# states/sec/chip, per-level exchange bytes, shard balance,
# host_syncs <= levels (supersteps) and window_recompiles == 0 —
# written to MULTICHIP_r08.json and gated per leg like multichip-check.
MULTICHIP_BENCH_DEVICES ?= 1,2,4,8
MULTICHIP_OUT ?= MULTICHIP_r08.json
multichip-bench:
	$(PY) -m jaxmc.meshbench bench \
	    --devices $(MULTICHIP_BENCH_DEVICES) \
	    --out $(MULTICHIP_OUT) --out-dir $(BENCH_CHECK_DIR)

# cross-model vmapped batching gate (ISSUE 13): the batchtoy cohort
# (one module, four cfgs differing only in liftable constant values)
# submitted cold must run as ONE vmapped engine — full occupancy, one
# engine build, per-member counts bit-identical to solo runs — at
# >= 2x the sequential cold aggregate states/sec (JAXMC_BATCH_GATE_X).
# The warm deep-rung pair is reported and baseline-gated (cpu-XLA's
# ~0.5ms dispatches leave little latency to amortize; the accelerator
# warm measurement is the standing driver-env task).  Prints a
# parseable `BATCH-CHECK SKIP: <reason>` where the leg cannot run.
batch-check:
	JAX_PLATFORMS=cpu $(PY) -m jaxmc.batchbench \
	    --out-dir $(BENCH_CHECK_DIR)
	# same-invocation throughput gate, kernelbench-style: artifacts
	# ordered [sequential, batched], so a batched cohort slower than
	# the sequential one raises the REGRESS states/sec flag (across-
	# run wall baselines are too noisy in shared containers; the
	# same-invocation ratio is load-independent)
	@if [ -f $(BENCH_CHECK_DIR)/jaxmc_batchbench_cold_seq.json ]; then \
	  echo "== batchbench cold cohort: sequential -> batched =="; \
	  $(PY) -m jaxmc.obs diff --fail-on-regress --threshold 25 \
	      '$(BENCH_CHECK_DIR)/jaxmc_batchbench_cold_*.json' \
	      || exit 1; \
	fi

# profiler/ledger gate (ISSUE 17): warm checkpoint-then-resume legs on
# transfer_scaled + symtoy_scaled under `--profile` — per-site walls
# must attribute >= 90% of the search wall (JAXMC_PROF_CHECK_MIN_SHARE
# overrides), profile-on vs profile-off counts must be bit-identical,
# and the legs' TEMP run ledger must pass `python -m jaxmc.obs history
# --fail-on-regress` (with a synthesized 2x slowdown proven to trip
# it).  Prints parseable `PROF-CHECK …` lines; SKIPs without jax.
prof-check:
	JAX_PLATFORMS=cpu $(PY) -m jaxmc.profcheck \
	    --out-dir $(BENCH_CHECK_DIR)

# checking-as-a-service smoke gate (ISSUE 7): fresh spool, in-process
# daemon, two identical jax-resident jobs — the second MUST reuse the
# warm session, resume the first job's final checkpoint, report
# window_recompiles == 0 and a capacity-profile hit, and its artifact
# must pass `python -m jaxmc.obs diff --fail-on-regress` against the
# cold one.  Exit 0 only when every assertion holds.
serve-check:
	JAX_PLATFORMS=cpu $(PY) -m jaxmc.serve smoke

# fleet-observability gate (ISSUE 16): in-process daemon + slow interp
# job with a fork pool + a device-owner jax job; GET /metrics must
# parse as Prometheus text with a MOVING per-job search.progress_est
# mid-run, GET /jobs/<id>/events must answer mid-run, warm counters
# must move on resubmission, and `obs timeline` over the daemon +
# per-job traces must stitch >= 3 distinct OS processes with ZERO
# orphan spans.  Exit 0 only when every assertion holds.
trace-check:
	JAX_PLATFORMS=cpu $(PY) -m jaxmc.tracecheck

# fleet-serving chaos gate (ISSUE 19): several subprocess daemons on
# ONE durable spool.  Legs: (takeover) SIGKILL the daemon that owns a
# slow job mid-run — a peer must steal the expired lease and finish
# from the spool checkpoint with counts bit-identical to a solo
# reference; (routing) identical submissions round-robined across 3
# ports must land on the sig-warm daemon, then `obs timeline
# --fail-on-orphans` must stitch every daemon + job trace with 0
# orphan spans; (admission) a depth-bounded daemon under a burst
# answers 429 + Retry-After with queue gauges while accepted jobs
# complete; (poison) a job whose owner always dies is quarantined
# after the cross-daemon retry budget with a named verdict.  Leg
# artifacts land in $(BENCH_CHECK_DIR) and the run ledger.  Prints
# one parseable `FLEET-CHECK SKIP: ...` line (exit 0) on hosts with
# < 2 CPUs or no bindable loopback port.
fleet-check:
	JAX_PLATFORMS=cpu $(PY) -m jaxmc.fleetbench \
	    --out-dir $(BENCH_CHECK_DIR)

# run the checking daemon on a durable spool (jobs/results/checkpoints
# survive restarts; SIGTERM drains gracefully — see README "Checking
# as a service")
SPOOL ?= /tmp/jaxmc_serve
serve:
	JAX_PLATFORMS=cpu $(PY) -m jaxmc.serve run --spool $(SPOOL)

bench-check-reset:
	rm -f $(BENCH_CHECK_DIR)/jaxmc_bench_check_serial.baseline.json \
	      $(BENCH_CHECK_DIR)/jaxmc_bench_check_par.baseline.json \
	      $(BENCH_CHECK_DIR)/jaxmc_bench_check_warmleg.baseline.json \
	      $(BENCH_CHECK_DIR)/jaxmc_bench_check_warm.ck \
	      $(BENCH_CHECK_DIR)/jaxmc_batchbench_cold_seq.json \
	      $(BENCH_CHECK_DIR)/jaxmc_batchbench_cold_batch.json \
	      $(BENCH_CHECK_DIR)/jaxmc_batchbench_warm_seq.json \
	      $(BENCH_CHECK_DIR)/jaxmc_batchbench_warm_batch.json

# build the native host fingerprint store (it builds itself on first
# use; this target only makes the build, or its failure, visible)
native:
	$(PY) -c "from jaxmc import native_store as n; assert n.is_available(), n.build_error()"

.PHONY: all check check-corpus test chaos bench-tlc \
        pin-si-env bench-check bench-check-reset serve serve-check \
        trace-check fleet-check batch-check multichip-check \
        multichip-bench backend-check por-check prof-check native \
        lint-corpus pylint
