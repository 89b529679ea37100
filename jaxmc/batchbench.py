r"""Cross-model batching bench leg (ISSUE 13): `python -m jaxmc.batchbench`.

The whole point of the vmapped multi-model engine is that a cohort of N
layout-compatible jobs costs ONE engine (one layout, one kernel set,
one XLA program) instead of N.  This driver turns that into a GATE over
the repo-local batchtoy family (one module, cfgs differing only in
liftable constant values), with two measured legs:

  COLD COHORT (the gated one — the serve acceptance scenario "N
  compatible jobs submitted cold -> one vmapped dispatch sequence"):
    sequential  each member pays its own full cold cost: model load,
                layout sampling, kernel build, XLA compile, search —
                the pre-PR-13 fleet's cost for a cold cohort;
    batched     ONE BatchCheckEngine: one donor build (union-sampled
                layout), one jit(vmap(hstep_core)) compile, one
                vmapped dispatch sequence.
    Aggregate cold states/sec must be >= GATE_X (default 2.0,
    JAXMC_BATCH_GATE_X) times sequential: compile/build amortization
    across the cohort is the dominant, reproducible fleet win on
    CPU-XLA containers.

  WARM DEEP RUNG (reported, informational — no gate):
    the batchtoy_bench* deep-narrow rungs, warm engines both sides,
    identical job options.  On CPU-XLA the per-dispatch overhead the
    vmapped sharing amortizes is ~0.5ms — the same order as the
    per-level host bookkeeping — so the warm same-option ratio sits
    near 1x in this container (measured 0.95-1.1x; BASELINE.md), and a
    wall-based gate would only measure machine noise (identical legs
    swing 2x run-to-run here).  The warm win is LATENCY-bound: it
    needs a device whose per-dispatch cost dwarfs the host bookkeeping.
    On an accelerator the cohort path is measured since PR 39 — not
    this leg but the served one, a commit's matrix of four cfgs as one
    cohort on a TPU v5e (the benchmark cell `ci-cohort-4p`; numbers in
    PERF.md sections 5 and 6 and in README "Continuous batching") —
    and what it shows is the other way round: a vmapped dispatch is a
    synchronous host round trip there and the device is idle nearly
    all of a commit.  The warm artifacts are written for
    inspection (`obs report`/`obs diff` by hand).

Per-member counts must be BIT-IDENTICAL between legs in BOTH scenarios
(batching is a throughput optimization, never a semantics change), and
the cold cohort must reach full occupancy (every member in one vmapped
program).  Environments where the leg cannot run (no jax, no native
store) print a parseable `BATCH-CHECK SKIP: <reason>` line and exit 0.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEFAULT_SPEC = os.path.join(_REPO, "specs", "batchtoy.tla")
COLD_CFGS = [os.path.join(_REPO, "specs", f"batchtoy_{v}.cfg")
             for v in ("a", "b", "c", "d")]
WARM_CFGS = [os.path.join(_REPO, "specs", f"batchtoy_bench{i}.cfg")
             for i in (1, 2, 3, 4)]


def _skip(reason: str) -> int:
    print(f"BATCH-CHECK SKIP: {reason}")
    return 0


def _artifact(path: str, leg: str, wall_s: float, distinct: int,
              generated: int, members: int, occupancy: int,
              dispatches: Optional[int], lifted: List[str]) -> None:
    from . import obs
    env = obs.environment_meta()
    env["platform"] = "cpu"
    gauges = {"batch.members": members,
              "batch.occupancy": occupancy,
              "batchbench.leg": leg,
              "batch.lifted_consts": lifted}
    if dispatches is not None:
        gauges["batch.dispatch_count"] = dispatches
    art = {
        "schema": "jaxmc.metrics/2",
        "started_at": time.time(),
        "wall_s": round(wall_s, 6),
        "backend": "jax",
        "spec": DEFAULT_SPEC,
        "phases": [{"name": "search", "wall_s": round(wall_s, 6),
                    "count": members}],
        "counters": {},
        "gauges": gauges,
        "levels": [],
        "env": env,
        "result": {"ok": True, "distinct": distinct,
                   "generated": generated, "diameter": 0,
                   "truncated": False, "wall_s": round(wall_s, 6)},
    }
    obs.write_json_atomic(path, art)
    # ISSUE 17: each gate leg lands a trajectory point in the run ledger
    obs.append_summary(art, source=path)


def _counts(r):
    return (r.ok, r.distinct, r.generated, r.diameter)


def _parity_or_fail(tag: str, cfgs, solo_results, members, log) -> bool:
    for c, sr, mem in zip(cfgs, solo_results, members):
        if mem.error is not None:
            log(f"BATCH-CHECK FAIL [{tag}]: member "
                f"{os.path.basename(c)} errored: {mem.error}")
            return False
        if _counts(sr) != _counts(mem.result):
            log(f"BATCH-CHECK FAIL [{tag}]: {os.path.basename(c)} "
                f"counts diverge: solo {_counts(sr)} vs batched "
                f"{_counts(mem.result)}")
            return False
    return True


def run_leg(spec: str, cold_cfgs: List[str], warm_cfgs: List[str],
            out_dir: str, log=print) -> int:
    try:
        import jax.numpy as jnp
    except ImportError:
        return _skip("jax is not importable in this environment")
    from . import native_store
    if not native_store.is_available():
        return _skip(f"native host store unavailable "
                     f"({native_store.build_error()})")
    from .backend.batch import BatchCheckEngine, BatchIncompatible
    from .backend.bfs import TpuExplorer
    from .session import SessionConfig, load_model

    # pay backend init once, outside every timed window
    jnp.zeros(8).block_until_ready()
    os.makedirs(out_dir, exist_ok=True)

    def sess(c):
        return SessionConfig(spec=spec, cfg=c, backend="jax",
                             platform="cpu", host_seen=True,
                             no_trace=True)

    # ---- COLD COHORT: N full solo colds vs one batched cold --------
    log(f"== batchbench cold cohort: {len(cold_cfgs)} members ==")
    seq_wall = 0.0
    seq_cold = []
    for c in cold_cfgs:
        t0 = time.time()
        m = load_model(spec, c, False)
        ex = TpuExplorer(m, host_seen=True, store_trace=False)
        r = ex.run()
        w = time.time() - t0
        seq_wall += w
        seq_cold.append(r)
        log(f"   solo cold {os.path.basename(c)}: {w:.2f}s "
            f"({r.distinct} distinct)")
    seq_gen = sum(r.generated for r in seq_cold)
    seq_dis = sum(r.distinct for r in seq_cold)
    seq_rate = seq_dis / max(seq_wall, 1e-9)

    t0 = time.time()
    try:
        be = BatchCheckEngine([sess(c) for c in cold_cfgs]).build()
    except BatchIncompatible as ex:
        log(f"BATCH-CHECK FAIL: cold fixture family not batchable "
            f"({ex})")
        return 1
    members = be.run()
    bat_wall = time.time() - t0
    if not _parity_or_fail("cold", cold_cfgs, seq_cold, members, log):
        return 1
    disp = be.dispatcher
    bat_gen = sum(m.result.generated for m in members)
    bat_dis = sum(m.result.distinct for m in members)
    bat_rate = bat_dis / max(bat_wall, 1e-9)
    if disp.max_width < len(cold_cfgs):
        log(f"BATCH-CHECK FAIL: cold occupancy {disp.max_width} < "
            f"{len(cold_cfgs)} (cohort did not share one program)")
        return 1
    cold_ratio = bat_rate / max(seq_rate, 1e-9)
    log(f"   sequential cold: {seq_wall:.2f}s "
        f"({seq_rate:,.0f} states/sec aggregate)")
    log(f"   batched cold:    {bat_wall:.2f}s "
        f"({bat_rate:,.0f} states/sec; occupancy={disp.max_width}, "
        f"one engine build, lifted={','.join(be.lift_names)})")
    _artifact(os.path.join(out_dir, "jaxmc_batchbench_cold_seq.json"),
              "cold-sequential", seq_wall, seq_dis, seq_gen,
              len(cold_cfgs), 1, None, list(be.lift_names))
    _artifact(os.path.join(out_dir, "jaxmc_batchbench_cold_batch.json"),
              "cold-batched", bat_wall, bat_dis, bat_gen,
              len(cold_cfgs), disp.max_width, disp.dispatches,
              list(be.lift_names))

    # ---- WARM DEEP RUNG: reported, regression-gated ----------------
    log(f"== batchbench warm deep rung: {len(warm_cfgs)} members ==")
    wseq_wall = 0.0
    wseq = []
    for c in warm_cfgs:
        m = load_model(spec, c, False)
        ex = TpuExplorer(m, host_seen=True, store_trace=False)
        ex.run()  # warm-up: compile, untimed
        t0 = time.time()
        r = ex.run()
        wseq_wall += time.time() - t0
        wseq.append(r)
    try:
        wbe = BatchCheckEngine([sess(c) for c in warm_cfgs]).build()
    except BatchIncompatible as ex:
        log(f"BATCH-CHECK FAIL: warm fixture family not batchable "
            f"({ex})")
        return 1
    wbe.run()  # warm-up: the one vmapped compile, untimed
    t0 = time.time()
    wmembers = wbe.run()
    wbat_wall = time.time() - t0
    if not _parity_or_fail("warm", warm_cfgs, wseq, wmembers, log):
        return 1
    warm_ratio = (sum(r.distinct for r in wseq) / max(wseq_wall, 1e-9))
    warm_ratio = (sum(m.result.distinct for m in wmembers)
                  / max(wbat_wall, 1e-9)) / max(warm_ratio, 1e-9)
    log(f"   warm sequential {wseq_wall:.2f}s vs batched "
        f"{wbat_wall:.2f}s -> {warm_ratio:.2f}x aggregate "
        f"states/sec")
    _artifact(os.path.join(out_dir, "jaxmc_batchbench_warm_seq.json"),
              "warm-sequential", wseq_wall,
              sum(r.distinct for r in wseq),
              sum(r.generated for r in wseq),
              len(warm_cfgs), 1, None, list(wbe.lift_names))
    _artifact(os.path.join(out_dir, "jaxmc_batchbench_warm_batch.json"),
              "warm-batched", wbat_wall,
              sum(m.result.distinct for m in wmembers),
              sum(m.result.generated for m in wmembers),
              len(warm_cfgs), wbe.dispatcher.max_width,
              wbe.dispatcher.dispatches, list(wbe.lift_names))

    # ---- the gate ---------------------------------------------------
    gate_x = float(os.environ.get("JAXMC_BATCH_GATE_X", "2.0"))
    verdict = "PASS" if cold_ratio >= gate_x else "FAIL"
    log(f"BATCH-CHECK {verdict}: cold cohort batched/sequential = "
        f"{cold_ratio:.2f}x (gate {gate_x:.1f}x) | warm deep rung = "
        f"{warm_ratio:.2f}x (cpu-XLA, informational) | occupancy "
        f"{disp.max_width}/{len(cold_cfgs)} | parity bit-identical")
    return 0 if verdict == "PASS" else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m jaxmc.batchbench",
        description="cross-model vmapped batching gate (ISSUE 13)")
    ap.add_argument("--spec", default=DEFAULT_SPEC)
    ap.add_argument("--cold-cfgs", nargs="*", default=COLD_CFGS)
    ap.add_argument("--warm-cfgs", nargs="*", default=WARM_CFGS)
    ap.add_argument("--out-dir", default="/tmp")
    args = ap.parse_args(argv)
    return run_leg(args.spec, list(args.cold_cfgs),
                   list(args.warm_cfgs), args.out_dir)


if __name__ == "__main__":
    sys.exit(main())
