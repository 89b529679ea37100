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

# static analysis gates (ISSUE 9):
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

# checking-as-a-service smoke gate (ISSUE 7): fresh spool, in-process
# daemon, two identical jax-resident jobs — the second MUST reuse the
# warm session, resume the first job's final checkpoint, report
# window_recompiles == 0 and a capacity-profile hit, and its artifact
# must pass `python -m jaxmc.obs diff --fail-on-regress` against the
# cold one.  Exit 0 only when every assertion holds.
serve-check:
	JAX_PLATFORMS=cpu $(PY) -m jaxmc.serve smoke

# run the checking daemon on a durable spool (jobs/results/checkpoints
# survive restarts; SIGTERM drains gracefully — see README "Checking
# as a service")
SPOOL ?= /tmp/jaxmc_serve
serve:
	JAX_PLATFORMS=cpu $(PY) -m jaxmc.serve run --spool $(SPOOL)

# build the native host fingerprint store (it builds itself on first
# use; this target only makes the build, or its failure, visible)
native:
	$(PY) -c "from jaxmc import native_store as n; assert n.is_available(), n.build_error()"

.PHONY: all check check-corpus test lint-corpus pylint chaos bench-tlc \
        pin-si-env serve serve-check native
