r"""The corpus sweep: `jaxmc sweep` = the reference's `make test` contract
(`tlc *tla`, /root/reference/Makefile:6-7) — check every checkable
spec+cfg with its EXPECTED verdict, including the models whose defining
property is an expected violation. One manifest drives both the sweep and
the pytest pins (tests/test_corpus.py parametrizes over it).

Verdicts: "ok" (clean pass), "assumes" (ASSUME-calculator module, no
behavior spec), or "violation:<kind>" where kind is the Violation.kind the
checker must report (invariant/property/assert/deadlock).

Statuses (VERDICT r2 weak #2): every case resolves to "pass", "fail", or
"skip" — SKIP is its OWN category, never a pass. The expected jax
compile-set is pinned per case (`jax="yes"`): a model that used to
compile on the jax backend and stops compiling is a FAILURE, not a
silent skip. `jaxmc sweep --backend jax` runs each case in a fresh
subprocess with a wall-clock timeout (JAXMC_SWEEP_TIMEOUT, default 900 s)
so one pathological XLA compile cannot wedge the whole sweep.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import List, Optional, Tuple

REFERENCE = os.environ.get("JAXMC_REFERENCE", "/root/reference")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SS = "examples/SpecifyingSystems"


@dataclass
class Case:
    spec: str                      # path, relative to root
    root: str = "ref"              # "ref" (reference) | "repo"
    cfg: Optional[str] = None      # defaults to spec with .cfg
    expect: str = "ok"             # ok | assumes | violation:<kind>
    distinct: Optional[int] = None
    generated: Optional[int] = None
    no_deadlock: bool = False
    includes: Tuple[str, ...] = ()  # extra -I dirs, relative to root kind
    slow: bool = False             # excluded from the default sweep/pins
    # the pinned jax compile-set: "yes" = must compile AND match the same
    # pins on the jax backend; "skip" = known outside the compilable
    # subset (recursion/CHOOSE-heavy — the interp remains its checker)
    jax: str = "skip"
    # the pinned EXPANSION MODE (ISSUE 5): "compiled" | "hybrid" |
    # "interp-arms" as observed in the r05 jax sweep. A case that SLIDES
    # toward the interpreter (compiled -> hybrid/interp-arms, hybrid ->
    # interp-arms) FAILS the sweep — a silent demotion is a perf
    # regression, not a pass. Cases pinned "interp-arms" skip kernel
    # construction entirely (TpuExplorer pin_interp_arms): building
    # kernels the engine immediately demotes burned 245s of the r05
    # sweep (213s on MCInnerSerial alone). JAXMC_MODE_PIN=0 lifts the
    # pins for one sweep — the diagnosis mode that builds everything
    # and logs each arm's demotion reason.
    mode: Optional[str] = None
    # DERIVED mode pin (ISSUE 15): for mode="interp-arms" cases whose
    # demotions the analyze/verdicts.py classification covers, the PREDICTOR
    # (not the measured pin) skips the futile kernel builds — and the
    # sweep asserts full coverage: a predictor that stops predicting
    # every arm FAILS the case loudly instead of silently re-paying
    # the builds the pin existed to kill (the MCInnerSerial 213s).
    # JAXMC_PIN_DERIVE=0 falls back to the measured pin for one sweep.
    pin_derived: bool = False
    # lane-capacity floors the default sampler under-observes for this
    # model (e.g. MCInnerSequential's opQ outgrows the sampled max):
    # passed to the device backend as Bounds(seq_cap=..., ...)
    seq_cap: Optional[int] = None
    grow_cap: Optional[int] = None
    kv_cap: Optional[int] = None
    # steady-state RESIDENT capacity buckets for this case (ISSUE 6):
    # the manifest-recorded floor for {SC, FCap, AccCap, VC} so a run
    # seeded from it (chip_smoke.py, tests/test_kernel2.py) compiles
    # ONCE and never grows mid-window.  The
    # persisted capacity profile (compile/cache.py) max-merges over
    # this; the manifest value is the committed, review-able record.
    res_caps: Optional[dict] = None
    # LINT surface (ISSUE 9, `make lint-corpus`): diagnostic codes this
    # pair is WAIVED for (intentional fixture constructs — each waiver
    # carries a comment at the case naming why), and, for lint-only
    # fixtures, the codes the pair MUST produce.  A lint_only case is
    # never swept/checked — it exists to exercise the linter.
    lint_waive: Tuple[str, ...] = ()
    lint_only: bool = False
    lint_expect: Tuple[str, ...] = ()

    def spec_path(self) -> str:
        base = REFERENCE if self.root == "ref" else REPO
        return os.path.join(base, self.spec)

    def cfg_path(self) -> Optional[str]:
        if self.cfg == "":
            return None
        if self.cfg is not None:
            base = REFERENCE if self.root == "ref" else REPO
            return os.path.join(base, self.cfg)
        p = self.spec_path()[:-4] + ".cfg"
        return p if os.path.exists(p) else None

    def include_dirs(self) -> List[str]:
        out = []
        for inc in self.includes:
            if inc.startswith("repo:"):
                out.append(os.path.join(REPO, inc[5:]))
            else:
                out.append(os.path.join(REFERENCE, inc))
        return out


# Every reference cfg (all 21) plus the repo's MC shims. Counts are the
# TLC-semantics pins (CONSTRAINT-violating states are discarded, matching
# the golden testout2 run; see tests/test_corpus.py).
CASES: List[Case] = [
    # -- top level + tutorial variants
    Case("pcal_intro.tla", distinct=3800, generated=5850, jax="yes",
         mode="compiled"),
    # JMC301 waived: the PlusCal translator emits Termination /
    # MoneyInvariant whether or not the (absent) cfg checks them
    Case("specs/pcal_intro_buggy.tla", root="repo", cfg="",
         expect="violation:assert", jax="yes", mode="compiled",
         lint_waive=("JMC301",)),
    Case("atomic_add.tla", cfg="", distinct=5, generated=7,
         no_deadlock=True, jax="yes", mode="compiled"),
    # -- Paxos chain
    Case("examples/Paxos/MCConsensus.tla", distinct=4, generated=7,
         no_deadlock=True, jax="yes", mode="compiled"),
    Case("examples/Paxos/MCVoting.tla", distinct=77, generated=406,
         no_deadlock=True, jax="yes", mode="compiled"),
    Case("examples/Paxos/MCPaxos.tla", distinct=25, generated=82,
         jax="yes", mode="compiled"),
    # -- Specifying Systems chapters
    Case(f"{SS}/SimpleMath/SimpleMath.tla", expect="assumes"),
    Case(f"{SS}/HourClock/HourClock.tla", distinct=12, generated=24,
         jax="yes", mode="compiled"),
    Case(f"{SS}/HourClock/HourClock2.tla", distinct=12, generated=24,
         jax="yes", mode="compiled"),
    Case(f"{SS}/AsynchronousInterface/AsynchInterface.tla",
         distinct=12, generated=30, jax="yes", mode="hybrid"),
    Case(f"{SS}/AsynchronousInterface/Channel.tla",
         distinct=12, generated=30, jax="yes", mode="compiled"),
    Case(f"{SS}/AsynchronousInterface/PrintValues.tla", expect="assumes"),
    Case(f"{SS}/FIFO/MCInnerFIFO.tla", distinct=3864, generated=9660,
         jax="yes", mode="compiled"),
    Case(f"{SS}/CachingMemory/MCInternalMemory.tla",
         distinct=4408, generated=21400, jax="yes", mode="hybrid"),
    Case(f"{SS}/CachingMemory/MCWriteThroughCache.tla",
         distinct=5196, generated=28170, jax="yes", mode="hybrid"),
    Case(f"{SS}/Liveness/LiveHourClock.tla", distinct=12, generated=24,
         jax="yes", mode="compiled"),
    Case(f"{SS}/Liveness/MCLiveInternalMemory.tla",
         distinct=4408, generated=21400, jax="yes", mode="hybrid"),
    Case(f"{SS}/Liveness/MCLiveWriteThroughCache.tla",
         distinct=5196, generated=28170, jax="yes", mode="hybrid"),
    # ErrorTemporal is EXPECTED to fail (MCRealTimeHourClock.tla:43)
    Case(f"{SS}/RealTime/MCRealTimeHourClock.tla",
         expect="violation:property", distinct=216, generated=696,
         jax="yes", mode="interp-arms"),
    Case(f"{SS}/TLC/ABCorrectness.tla", distinct=20, generated=36,
         jax="yes", mode="compiled"),
    Case(f"{SS}/TLC/MCAlternatingBit.tla", distinct=240, generated=1392,
         jax="yes", mode="compiled"),
    Case(f"{SS}/AdvancedExamples/MCInnerSequential.tla",
         distinct=3528, generated=24368, jax="yes", seq_cap=8,
         mode="compiled"),
    # the golden testout2 model (6181/195, diameter 5 — TLC 1.57: 22h).
    # testout1 (the 17h log) is a SECOND run of this SAME model: both
    # logs open "4 distinct initial states" and climb to 195 distinct at
    # diameter 5; testout1 was cut off at 6032 generated with 2 states
    # on queue (no final-totals line), consistent with this 6181 final —
    # so this pin covers BOTH golden logs
    # interp-arms PINNED (ISSUE 5): the r05 sweep burned 213s building
    # 13 kernels that all demoted (the recursion in Serializable/
    # opOrder reaches every arm through the inlined response guards).
    # The pin skips kernel construction outright; run a sweep with
    # JAXMC_MODE_PIN=0 to rebuild everything and log each arm's
    # demotion reason (the path to compiling the mechanical
    # request/response arms while recursion stays demoted)
    # pin DERIVED since ISSUE 15: the recursive-operator verdict class
    # covers every arm (opOrder reaches each through the inlined
    # response guards), so the predictor skips the builds and the
    # sweep asserts it keeps doing so (JAXMC_PIN_DERIVE=0 restores the
    # measured pin for a diagnosis sweep)
    Case(f"{SS}/AdvancedExamples/MCInnerSerial.tla",
         distinct=195, generated=6181, jax="yes", mode="interp-arms",
         pin_derived=True),
    # the shipped alternative model (Proc={p1}, DataInvariant only):
    # matches NEITHER golden log (they both record 4 init states; this
    # model has 2) — counts below are this repo's cross-backend pin,
    # closing the last unswept reference cfg (21/21)
    Case(f"{SS}/AdvancedExamples/MCInnerSerial.tla",
         cfg=f"{SS}/AdvancedExamples/MCInnerSerial.cfg.alt",
         distinct=9, generated=47, jax="yes", mode="interp-arms",
         pin_derived=True),
    # -- repo MC shims for the cfg-less reference specs
    Case("specs/transfer_scaled.tla", root="repo",
         cfg="specs/transfer_scaled.cfg",
         distinct=153701, generated=311153, slow=True, jax="yes",
         mode="compiled",
         # steady resident buckets (ISSUE 6) so the warm-up compile
         # covers the whole run
         res_caps={"SC": 1 << 18, "FCap": 1 << 16, "AccCap": 1 << 17,
                   "VC": 1 << 13, "chunk": 2048}),
    # the chip's REAL rung (ISSUE 21, chip_smoke.py legs B/C/E): four
    # processes.  Counts confirmed by the exact interpreter (--workers
    # 8, 686 s here): 13 BFS levels, largest frontier 1,883,904.
    # res_caps are bench/pins/transfer_scaled_4p.json's (ISSUE 30): the
    # widest level's 4,873,404 candidates + VC need AccCap 2^23, the
    # largest frontier FCap 2^22 — smaller ones cost a regrowth and its
    # recompile (~60 s on the chip) in every run seeded from here
    Case("specs/transfer_scaled.tla", root="repo",
         cfg="specs/transfer_scaled_4p.cfg",
         distinct=9394019, generated=24035597, slow=True, jax="yes",
         mode="compiled",
         res_caps={"SC": 1 << 24, "FCap": 1 << 22, "AccCap": 1 << 23,
                   "VC": 1 << 14, "chunk": 2048}),
    # the FLOOR rung (ISSUE 21): what chip_smoke.py's leg B runs —
    # the real rung's cold resident run alone costs more XLA compile
    # time than the smoke's 1200 s contract leaves (PERF.md).  Counts
    # confirmed by the exact interpreter: 13 levels, largest frontier
    # 374,504
    Case("specs/transfer_scaled.tla", root="repo",
         cfg="specs/transfer_scaled_4p8.cfg",
         distinct=1859252, generated=4767576, slow=True, jax="yes",
         mode="compiled"),
    Case("specs/MCraftMicro.tla", root="repo",
         cfg="specs/MCraft_micro.cfg", includes=("examples",),
         distinct=694, generated=6185, jax="yes", mode="compiled",
         res_caps={"SC": 1 << 12, "FCap": 1 << 9, "AccCap": 1 << 12,
                   "VC": 1 << 11, "chunk": 256}),
    # mode=compiled proven by the BENCH_r02 resident-mode completion
    # (resident refuses hybrid/interp-arms outright)
    Case("specs/MCraftMicro.tla", root="repo",
         cfg="specs/MCraft_3s_bench.cfg", includes=("examples",),
         distinct=76654, generated=1138651, slow=True, jax="yes",
         mode="compiled",
         # the full rung's steady caps (one warm-up compile
         # covers the run; the persisted profile max-merges over this)
         res_caps={"SC": 1 << 18, "FCap": 1 << 16, "AccCap": 1 << 17,
                   "VC": 1 << 13}),
    Case("specs/MCtextbookSI.tla", root="repo",
         cfg="specs/MCtextbookSI_small.cfg", includes=("examples",),
         distinct=569, generated=945, jax="yes", mode="interp-arms"),
    # SI is EXPECTED non-serializable (textbookSnapshotIsolation.tla:91-96)
    Case("specs/MCtextbookSI.tla", root="repo",
         cfg="specs/MCtextbookSI_skew.cfg", includes=("examples",),
         expect="violation:invariant", slow=True),
    Case("specs/MCserializableSI.tla", root="repo",
         cfg="specs/MCserializableSI_small.cfg", includes=("examples",),
         distinct=569, generated=945, jax="yes", mode="interp-arms"),
    # fast-CI seeded write-skew: SI MUST reach a non-serializable history
    # (textbookSnapshotIsolation.tla:91-96; VERDICT r2 weak #3)
    Case("specs/MCtextbookSI.tla", root="repo",
         cfg="specs/MCtextbookSI_skew_fast.cfg", includes=("examples",),
         expect="violation:invariant", jax="yes", mode="interp-arms"),
    # SSI at its documented envelope floor (2 keys x 3 txns, seeded):
    # serializability HOLDS while write skew is attempted and aborted
    Case("specs/MCserializableSI.tla", root="repo",
         cfg="specs/MCserializableSI_env.cfg", includes=("examples",),
         slow=True),
    # VIEW/CONSTRAINT parity fixtures (PR 3), now first-class manifest
    # cases: cfg VIEW compiles on the jax backend since ISSUE 6 (dedup
    # keys on the compiled view's value lanes), both with committed
    # res_caps records
    Case("specs/viewtoy.tla", root="repo", cfg="specs/viewtoy.cfg",
         distinct=5, generated=11, jax="yes", mode="compiled",
         res_caps={"SC": 256, "FCap": 64, "AccCap": 128, "VC": 64,
                   "chunk": 64}),
    # JMC301 waived: AssertBound is a deliberate spare CONSTRAINT the
    # parity tests swap in for the Assert-raising discard path
    Case("specs/constoy.tla", root="repo", cfg="specs/constoy.cfg",
         distinct=21, generated=43, jax="yes", mode="compiled",
         lint_waive=("JMC301",),
         res_caps={"SC": 256, "FCap": 64, "AccCap": 128, "VC": 64,
                   "chunk": 64}),
    # cross-model batching fixture family (ISSUE 13): one module, four
    # cfgs differing ONLY in liftable constant values — layout-
    # compatible by construction, so the serve fleet and
    # tests/test_batch.py can prove the vmapped multi-model engine in
    # containers without /root/reference.  batchtoy_bad's Bound sits
    # below the reachable x maximum: the mixed-batch scenario (one
    # member violates, the rest run to exhaustion).
    Case("specs/batchtoy.tla", root="repo",
         cfg="specs/batchtoy_a.cfg",
         distinct=28, generated=29, jax="yes", mode="compiled"),
    Case("specs/batchtoy.tla", root="repo",
         cfg="specs/batchtoy_b.cfg",
         distinct=40, generated=41, jax="yes", mode="compiled"),
    Case("specs/batchtoy.tla", root="repo",
         cfg="specs/batchtoy_c.cfg",
         distinct=20, generated=21, jax="yes", mode="compiled"),
    Case("specs/batchtoy.tla", root="repo",
         cfg="specs/batchtoy_d.cfg",
         distinct=32, generated=33, jax="yes", mode="compiled"),
    Case("specs/batchtoy.tla", root="repo",
         cfg="specs/batchtoy_bad.cfg",
         expect="violation:invariant", jax="yes", mode="compiled"),
    # bench-scale rungs (ISSUE 6): wide-shallow variants of the
    # VIEW/SYMMETRY fixtures; tests/test_kernel2.py holds the packed
    # resident kernel to these pins, tests/test_mesh_session.py the
    # sharded engine at D=2 and D=4
    Case("specs/viewtoy_scaled.tla", root="repo",
         cfg="specs/viewtoy_scaled.cfg",
         distinct=18432, generated=239617, jax="yes", mode="compiled",
         res_caps={"SC": 1 << 15, "FCap": 1 << 12, "AccCap": 1 << 15,
                   "VC": 1 << 13, "chunk": 1024}),
    # out-of-core overflow fixture (ISSUE 12): a wide-state rung whose
    # exact dedup keys cost >7x a fingerprint; tests/test_tiers.py
    # forces a device seen cap at ~17% of its state count and pins the
    # capped (tier-spilling) and fingerprint-mode runs bit-identical to
    # this uncapped record.  NoMeet (the ooc_scaled_bad.cfg violation rung)
    # is deliberately unused here — JMC301 waived.
    Case("specs/ooc_scaled.tla", root="repo",
         cfg="specs/ooc_scaled.cfg",
         distinct=3072, generated=12289, jax="yes", mode="compiled",
         lint_waive=("JMC301",),
         res_caps={"SC": 1 << 13, "FCap": 256, "AccCap": 1 << 10,
                   "VC": 512, "chunk": 256}),
    Case("specs/symtoy_scaled.tla", root="repo",
         cfg="specs/symtoy_scaled.cfg", no_deadlock=True,
         distinct=10725, generated=65365, jax="yes", mode="compiled",
         res_caps={"SC": 1 << 15, "FCap": 1 << 12, "AccCap": 1 << 14,
                   "VC": 1 << 13, "chunk": 1024}),
    # SYMMETRY over a real group in the SORTED form (ISSUE 47,
    # compile/symmetry2.py): the transfer race under Permutations(Procs),
    # S3 and S5.  Counts are TLC's with symmetry — every initial state
    # generated, orbits distinct — as bench/reference/
    # transfer_symmetry.py and the exact interpreter give them
    # (tests/test_symmetry_sort.py); unreduced 5,799 and 545,822 distinct
    Case("specs/transfer_symmetry.tla", root="repo",
         cfg="specs/transfer_symmetry_3p4.cfg",
         distinct=1148, generated=2369, jax="yes", mode="compiled",
         res_caps={"SC": 1 << 12, "FCap": 256, "AccCap": 1 << 11,
                   "VC": 512, "chunk": 256}),
    Case("specs/transfer_symmetry.tla", root="repo",
         cfg="specs/transfer_symmetry_5p3.cfg",
         distinct=9336, generated=29382, jax="yes", mode="compiled",
         res_caps={"SC": 1 << 14, "FCap": 1 << 11, "AccCap": 1 << 14,
                   "VC": 1 << 12, "chunk": 512}),
    # a spec bounded by the cfg's CONSTRAINT alone (ISSUE 51): the
    # transfer race with a retry loop whose counter nothing in the spec
    # bounds.  Counts are TLC's under a CONSTRAINT — a discarded successor
    # is generated and fingerprinted, not distinct — as bench/reference/
    # transfer_retry.py and the exact interpreter give them
    # (tests/test_retry_constraint.py); 3,219 and 366 rows discarded
    Case("specs/transfer_retry.tla", root="repo",
         cfg="specs/transfer_retry_3p.cfg",
         distinct=5515, generated=16553, jax="yes", mode="compiled",
         res_caps={"SC": 1 << 14, "FCap": 1 << 10, "AccCap": 1 << 12,
                   "VC": 512, "chunk": 256}),
    Case("specs/transfer_retry.tla", root="repo",
         cfg="specs/transfer_retry_2p.cfg",
         distinct=1289, generated=2587, jax="yes", mode="compiled",
         res_caps={"SC": 1 << 12, "FCap": 256, "AccCap": 1 << 10,
                   "VC": 256, "chunk": 128}),
    # device SYMMETRY toys (orbit-canonical counts; deadlock expected
    # when every process exhausts its turns)
    Case("specs/symtoy.tla", root="repo", cfg="specs/symtoy.cfg",
         no_deadlock=True, distinct=22, generated=33, jax="yes",
         mode="compiled",
         res_caps={"SC": 256, "FCap": 64, "AccCap": 128, "VC": 64,
                   "chunk": 64}),
    # ISSUE 5 disclosure fixtures (repo-local, no reference needed):
    # identity-group SYMMETRY must say sym=identity, never claim an
    # UNREDUCED-FALLBACK divergence...
    Case("specs/symid.tla", root="repo", cfg="specs/symid.cfg",
         distinct=4, generated=4, jax="yes", mode="compiled"),
    # ...and an arm whose unguarded SUBSET-of-symbolic-set assignment
    # demotes AT BUILD TIME with a NAMED per-arm reason — the
    # repo-local representative of the hybrid class, pinning the
    # mode-slide failure path
    Case("specs/interparm_toy.tla", root="repo",
         cfg="specs/interparm_toy.cfg", distinct=19, generated=29,
         jax="yes", mode="hybrid"),
    # POR fixture family (ISSUE 15): independent per-element counters,
    # so the Step arms pairwise commute (analyze/independence.py) and
    # the --por persistent-set filter gets its measured reduction.
    # Unreduced counts pinned here; tests/test_independence.py runs the
    # reduced legs: verdict parity + >=30% explored-state reduction.
    # JMC301 waived on all three: Bounded/NoFire are deliberate spare
    # predicates — each cfg checks the subset its rung needs
    Case("specs/portoy.tla", root="repo", cfg="specs/portoy.cfg",
         expect="violation:deadlock", distinct=80, generated=185,
         jax="yes", mode="compiled", lint_waive=("JMC301",)),
    Case("specs/portoy.tla", root="repo", cfg="specs/portoy_ok.cfg",
         no_deadlock=True, distinct=150, generated=366,
         jax="yes", mode="compiled", lint_waive=("JMC301",)),
    # jax engines report the level-batched violation (counts differ
    # from the interp's mid-level stop by design): verdict-only pin
    Case("specs/portoy.tla", root="repo", cfg="specs/portoy_bad.cfg",
         expect="violation:invariant", jax="yes", mode="compiled",
         lint_waive=("JMC301",)),
    # raft-shaped dynamic-key fixture (ISSUE 18): per-process message
    # table msgs[self] (element-commuting Send arms), a DYNAMIC \E arm
    # whose binder key resolves to a domain key set, and a CONSTANT-
    # keyed element read.  Unreduced counts pinned here; the device POR
    # test holds >=30% reduction with por.engine=device
    Case("specs/msgstoy.tla", root="repo", cfg="specs/msgstoy.cfg",
         no_deadlock=True, distinct=324, generated=1108,
         jax="yes", mode="compiled"),
    # DERIVED interp-arms fixture (ISSUE 15): both arms are unsized
    # dynamic \E shapes (multi-binder / nested) that the verdict
    # classification predicts with ground.py's exact reason strings — the
    # repo-local pin_derived representative (no /root/reference needed)
    Case("specs/dyntoy.tla", root="repo", cfg="specs/dyntoy.cfg",
         distinct=8, generated=49, jax="yes", mode="interp-arms",
         pin_derived=True),
    # LINT-ONLY fixture (ISSUE 9): deliberately unclean — a dead
    # action, an unused CONSTANT/VARIABLE/definition, a cfg naming an
    # undefined invariant, an unassigned CONSTANT, and a CHOOSE over
    # the symmetry set.  `make lint-corpus` asserts every expected
    # diagnostic class fires; no search ever runs it.
    Case("specs/linttoy.tla", root="repo", cfg="specs/linttoy.cfg",
         lint_only=True,
         lint_expect=("JMC101", "JMC102", "JMC201", "JMC202",
                      "JMC203", "JMC301", "JMC302")),
]

# mode-slide severity order: a case may only move LEFT (toward
# "compiled") without failing its pin
_MODE_ORDER = {"compiled": 0, "hybrid": 1, "interp-arms": 2}


def mode_pins_enabled() -> bool:
    """The JAXMC_MODE_PIN=0 escape hatch: one sweep with every pin
    lifted builds every kernel again and logs per-arm demotion reasons
    — the diagnosis pass for un-demoting arms."""
    return os.environ.get("JAXMC_MODE_PIN", "1") != "0"


def case_for_cfg(cfg_basename: str) -> Optional[Case]:
    """Manifest lookup by cfg basename (the tests and chip_smoke.py
    assert their counts against the pinned totals)."""
    for c in CASES:
        p = c.cfg_path()
        if p and os.path.basename(p) == cfg_basename:
            return c
    return None


def run_case(case: Case, backend: str = "interp"):
    """Returns (status, detail, result|None, mode|None); status is
    'pass' | 'fail' | 'skip'; mode (jax backend only) is the expansion
    execution mode — 'compiled' | 'hybrid' | 'interp-arms'.
    SKIP only arises on the jax backend, only for cases the
    manifest does NOT pin into the compile-set (jax='yes'): a pinned
    case that stops compiling FAILS (VERDICT r2 weak #2)."""
    from .front.cfg import ModelConfig, parse_cfg
    from .sem.modules import Loader, bind_model
    from .engine.explore import Explorer

    if case.lint_only:
        return "skip", ("lint-only fixture (make lint-corpus checks "
                        "it); not a checkable model"), None, None
    spec = case.spec_path()
    cfgp = case.cfg_path()
    if cfgp:
        with open(cfgp) as fh:
            cfg = parse_cfg(fh.read())
    else:
        cfg = ModelConfig(specification="Spec")
    if case.no_deadlock:
        cfg.check_deadlock = False
    ldr = Loader([os.path.dirname(spec)] + case.include_dirs())
    mod = ldr.load_path(spec)

    if case.expect == "assumes":
        from .sem.eval import eval_expr, _bool, Ctx
        from .sem.modules import bind_model_defs
        defs = bind_model_defs(mod, cfg)
        ctx = Ctx(defs)
        n = 0
        for a in mod.assumes:
            if not _bool(eval_expr(a.expr, ctx), "ASSUME"):
                return "fail", "ASSUME violated", None, None
            n += 1
        return "pass", f"{n} assumptions checked", None, None

    model = bind_model(mod, cfg)
    note = ""
    mode = None
    if backend == "jax":
        from .backend.bfs import TpuExplorer
        from .compile.vspec import Bounds, CompileError, ModeError
        from . import native_store
        b = Bounds()
        if case.seq_cap:
            b.seq_cap = case.seq_cap
        if case.grow_cap:
            b.grow_cap = case.grow_cap
        if case.kv_cap:
            b.kv_cap = case.kv_cap
        pin = case.mode if mode_pins_enabled() else None
        if pin is not None and pin not in _MODE_ORDER:
            # a typo'd pin must not silently disable enforcement (every
            # real mode would read as an "improvement" against it)
            return "fail", (f"manifest defect: unknown mode pin {pin!r} "
                            f"(expected one of "
                            f"{sorted(_MODE_ORDER)})"), None, None
        # DERIVED pin (ISSUE 15): the predictor, not the measured pin,
        # skips the futile builds — unless the operator lifted it
        # (JAXMC_PIN_DERIVE=0) or disabled prediction outright
        from . import analyze as _analyze
        derive = (case.pin_derived and pin == "interp-arms"
                  and os.environ.get("JAXMC_PIN_DERIVE", "1") != "0"
                  and _analyze.predict_enabled())
        try:
            # instrument compile cost (VERDICT r3 weak #3): construction
            # = grounding + kernel build + forced abstract tracing;
            # the run then adds the XLA compiles proper
            t_c0 = time.time()
            ex = TpuExplorer(model, store_trace=False, bounds=b,
                             host_seen=native_store.is_available(),
                             pin_interp_arms=(pin == "interp-arms"
                                              and not derive))
            build_s = time.time() - t_c0
            # honest per-case execution-mode disclosure (VERDICT r4
            # weak #3/#6): how much of the EXPANSION hot loop actually
            # runs compiled, and whether cfg SYMMETRY is device-reduced
            # or silently unreduced (divergence-by-design from TLC)
            n_arms = len(ex.arms)
            n_fb = len(ex.fb_arms)
            if n_fb == 0:
                mode = "compiled"
            elif ex.A > 0:
                mode = "hybrid"
            else:
                mode = "interp-arms"  # device does hashing/dedup only
            # symmetry disclosure, three-way (ISSUE 5 satellite):
            # build_canon2 returns None BY DESIGN for identity groups
            # (symmetry2.py) — no reduction exists to diverge from, so
            # sym=identity; only a genuine CompileError fallback
            # (ex._sym_fallback) claims divergence. MCPaxos's line used
            # to report a divergence that does not exist.
            sym_note = ""
            if model.symmetry is not None:
                if ex.canon_fn is not None:
                    sym_note = f", sym=device-reduced ({ex.sym_form})"
                elif ex._sym_fallback:
                    sym_note = (", sym=UNREDUCED-FALLBACK (counts "
                                "diverge from TLC's reduced ones)")
                else:
                    sym_note = (", sym=identity (every declared "
                                "permutation is the identity; counts "
                                "match TLC)")
            note = (f" [build {build_s:.1f}s, mode={mode}, "
                    f"A={ex.A} compiled instances, "
                    f"{n_arms - n_fb}/{n_arms} arms compiled, "
                    f"W={ex.W} lanes"
                    + (f", {n_fb} arms interp-demoted"
                       if ex.fb_arms else "")
                    + (f", {len(ex.fb_invs)} invs interp-demoted"
                       if ex.fb_invs else "") + sym_note
                    + (" [mode pinned]" if pin == "interp-arms" else "")
                    + "]")
            # per-arm demotion reason table (VERDICT r5 #4): name each
            # demoted arm and why — the evidence needed to un-demote
            # mechanical arms — instead of only a count
            if ex.fb_arms and pin != "interp-arms":
                reasons = "; ".join(
                    f"{a.label or 'Next'}: {reason[:100]}"
                    for a, reason in ex.fb_arms[:8])
                more = len(ex.fb_arms) - 8
                note += (f" [demoted arms: {reasons}"
                         + (f"; +{more} more" if more > 0 else "") + "]")
            # derived-pin coverage assertion (ISSUE 15): the measured
            # pin stays as the fallback CONTRACT — if the predictor
            # stops predicting every arm, the futile builds the pin
            # existed to kill are back, and the sweep says so loudly
            if derive:
                if len(ex.arm_verdicts) < len(ex.arms):
                    return "fail", (
                        f"PREDICTOR REGRESSION: pin_derived case "
                        f"predicted only {len(ex.arm_verdicts)}/"
                        f"{len(ex.arms)} arm demotions — the measured "
                        f"interp-arms pin would have skipped every "
                        f"build (diagnose with JAXMC_PIN_DERIVE=0)"
                        f"{note}"), None, mode
                note += " [pin derived by predictor]"
            # mode-pin enforcement BEFORE the run: a slide toward the
            # interpreter fails fast — no point paying the search for a
            # case whose compile coverage already regressed
            if pin is not None and mode != pin:
                if _MODE_ORDER.get(mode, 3) > _MODE_ORDER.get(pin, 3):
                    return "fail", (
                        f"REGRESSION: expansion mode slid from pinned "
                        f"'{pin}' to '{mode}'{note}"), None, mode
                note += (f" [mode improved vs pinned '{pin}' — update "
                         f"the manifest]")
            r = ex.run()
        except (CompileError, ModeError) as ex:
            if isinstance(ex, ModeError) and "hybrid" in str(ex) \
                    and not native_store.is_available():
                # a host capability gap, not a code regression: hybrid
                # pins need the native store's host_seen mode
                return "skip", (f"hybrid needs the native store "
                                f"(unavailable on this host): "
                                f"{ex}"), None, None
            if case.jax == "yes":
                return "fail", (f"REGRESSION: pinned into the jax "
                                f"compile-set but no longer compiles "
                                f"({ex})"), None, None
            return "skip", f"outside jax subset: {ex}", None, None
        if case.jax != "yes":
            note += " [compiles despite jax='skip' — update the manifest]"
    else:
        r = Explorer(model).run()

    if case.expect == "ok":
        if not r.ok:
            return "fail", f"unexpected {r.violation.kind} violation " \
                           f"({r.violation.name})", r, mode
    else:
        kind = case.expect.split(":", 1)[1]
        if r.ok or r.violation.kind != kind:
            return "fail", f"expected a {kind} violation, got " \
                           f"{'ok' if r.ok else r.violation.kind}", r, mode
    if case.distinct is not None and r.distinct != case.distinct:
        return "fail", f"distinct {r.distinct} != pinned " \
                       f"{case.distinct}", r, mode
    if case.generated is not None and r.generated != case.generated:
        return "fail", f"generated {r.generated} != " \
                       f"pinned {case.generated}", r, mode
    return "pass", f"{r.generated} generated / {r.distinct} distinct " \
                   f"({case.expect}){note}", r, mode


def _run_case_isolated(idx: int, backend: str, timeout_s: float):
    """One case in a fresh subprocess (CPU-pinned before first jax use)
    under a wall-clock timeout: one pathological XLA compile must not
    wedge the sweep (the round-2 jax sweep never finished on a 1-core
    box). Timeout is a FAILURE for jax='yes' cases, a skip otherwise."""
    import json
    import subprocess
    import sys
    cache_line = ""
    if backend == "jax":
        # persistent compile cache ON BY DEFAULT for sweep children
        # (ISSUE 5): repeat sweeps — and the repeat-spec pairs inside
        # one sweep (MCInternalMemory/MCLiveInternalMemory, the two
        # WriteThroughCache models) — reload their XLA programs from
        # disk instead of recompiling. enable_guarded_cache honors the
        # JAXMC_COMPILE_CACHE=off opt-out and degrades COLD on a
        # wedged/corrupt/foreign cache; the health probe is paid once
        # per cache dir per hour, not per case. The guard verdict rides
        # a JAXMC_CACHE_GUARD stdout line so a cold fallback is VISIBLE
        # in the sweep log instead of vanishing into NullTelemetry.
        cache_line = (
            "from jaxmc import obs as _obs\n"
            "from jaxmc.compile.cache import enable_guarded_cache\n"
            "_ct = _obs.Telemetry()\n"
            "enable_guarded_cache(tel=_ct)\n"
            "print('JAXMC_CACHE_GUARD ' + str(_ct.gauges.get("
            "'compile.persistent_cache_guard')))\n")
    code = (
        "import json, sys\n"
        "import jax\n"
        f"jax.config.update('jax_platforms', "
        f"{os.environ.get('JAXMC_SWEEP_PLATFORM', 'cpu')!r})\n"
        + cache_line +
        "from jaxmc.corpus import CASES, run_case\n"
        f"s, d, _, md = run_case(CASES[{idx}], backend={backend!r})\n"
        "print('JAXMC_CASE ' + json.dumps([s, d, md]))\n")
    case = CASES[idx]
    try:
        p = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True,
                           timeout=timeout_s,
                           cwd=REPO, env=dict(os.environ,
                                              PYTHONPATH=REPO))
    except subprocess.TimeoutExpired:
        if case.jax == "yes":
            return "fail", (f"REGRESSION: pinned into the jax compile-set "
                            f"but timed out after {timeout_s:.0f}s"), None
        return "skip", f"timed out after {timeout_s:.0f}s (compile?)", None
    guard_note = ""
    for line in (p.stdout or "").splitlines():
        if line.startswith("JAXMC_CACHE_GUARD ") and \
                "cold-fallback" in line:
            # a guard cold-fallback must be visible in the sweep log,
            # not silent: the whole-sweep wall-time win depends on it
            guard_note = (" [compile cache COLD: "
                          + line[len("JAXMC_CACHE_GUARD "):][:120] + "]")
    for line in (p.stdout or "").splitlines():
        if line.startswith("JAXMC_CASE "):
            s, d, md = json.loads(line[len("JAXMC_CASE "):])
            return s, d + guard_note, md
    tail = (p.stderr or "").strip().splitlines()[-1:] or ["no output"]
    return "fail", f"CRASH rc={p.returncode}: {tail[0][:160]}", None


def sweep(backend: str = "interp", include_slow: bool = False,
          log=print, isolate: Optional[bool] = None,
          metrics_out: Optional[str] = None) -> int:
    """Check the whole corpus; returns the number of failures.
    Logs explicit pass/violation/skip/fail tallies — a sweep where every
    model skips is visibly NOT a clean sweep. With metrics_out (or env
    JAXMC_SWEEP_METRICS_OUT) the per-case record — status, wall time,
    expansion mode — lands in a JSON artifact so future SWEEP logs carry
    a machine-readable phase breakdown, not only free text."""
    if isolate is None:
        isolate = backend == "jax" and \
            os.environ.get("JAXMC_SWEEP_INPROC") != "1"
    if metrics_out is None:
        metrics_out = os.environ.get("JAXMC_SWEEP_METRICS_OUT") or None
    timeout_s = float(os.environ.get("JAXMC_SWEEP_TIMEOUT", "900"))
    tallies = {"pass": 0, "fail": 0, "skip": 0}
    modes = {"compiled": 0, "hybrid": 0, "interp-arms": 0}
    expected_violations = 0
    case_records = []
    t0 = time.time()
    n = 0
    for i, case in enumerate(CASES):
        if case.slow and not include_slow:
            continue
        if case.lint_only:
            continue  # `make lint-corpus` owns these fixtures
        n += 1
        name = case.cfg or case.spec
        t1 = time.time()
        mode = None
        try:
            if isolate:
                status, detail, mode = _run_case_isolated(
                    i, backend, timeout_s)
            else:
                status, detail, _, mode = run_case(case, backend)
        except Exception as ex:  # a crash is a failure, not an abort
            status, detail = "fail", f"CRASH {type(ex).__name__}: {ex}"
        tag = {"pass": "ok  ", "fail": "FAIL", "skip": "SKIP"}[status]
        log(f"[{tag}] {name:62s} {detail} "
            f"({time.time() - t1:.1f}s)")
        tallies[status] += 1
        if status == "pass" and case.expect.startswith("violation"):
            expected_violations += 1
        if mode in modes:
            modes[mode] += 1
        case_records.append({"case": name, "status": status,
                             "expect": case.expect, "mode": mode,
                             "wall_s": round(time.time() - t1, 3),
                             "detail": detail})
    # advisor r3: disclose the platform isolated cases were pinned to —
    # `sweep --backend jax` on a TPU machine validates the CPU path
    # unless JAXMC_SWEEP_PLATFORM says otherwise, and the summary must
    # say which one actually ran
    plat_note = ""
    if isolate:
        plat_note = (", platform="
                     f"{os.environ.get('JAXMC_SWEEP_PLATFORM', 'cpu')}"
                     " [JAXMC_SWEEP_PLATFORM]")
    if backend == "jax" and not mode_pins_enabled():
        plat_note += ", MODE PINS LIFTED [JAXMC_MODE_PIN=0]"
    mode_note = ""
    if backend == "jax" and sum(modes.values()):
        # the honest coverage split (VERDICT r4 weak #3): "passes on the
        # jax backend" spans fully-compiled expansion, hybrid (some arms
        # interp-demoted), and all-interp-arms (device hashing/dedup only)
        mode_note = (f"; expansion modes: {modes['compiled']} "
                     f"fully-compiled / {modes['hybrid']} hybrid / "
                     f"{modes['interp-arms']} all-interp-arms")
    log(f"{n} corpus models: {tallies['pass']} pass "
        f"({expected_violations} expected-violation), "
        f"{tallies['skip']} SKIP (outside jax subset), "
        f"{tallies['fail']} FAIL "
        f"({time.time() - t0:.1f}s, backend={backend}{plat_note})"
        f"{mode_note}")
    if metrics_out:
        from . import obs
        art = {"schema": "jaxmc.sweep-metrics/1", "backend": backend,
               "isolated": bool(isolate),
               "platform": os.environ.get("JAXMC_SWEEP_PLATFORM", "cpu")
               if isolate else None,
               "wall_s": round(time.time() - t0, 3),
               "tallies": dict(tallies, total=n,
                               expected_violations=expected_violations),
               "modes": modes, "cases": case_records}
        obs.write_json_atomic(metrics_out, art)
        log(f"sweep metrics written to {metrics_out}")
    return tallies["fail"]
