r"""Cross-run metrics reporting: `python -m jaxmc.obs
{report,diff,timeline,top,history}`.

PR 1 made one run legible (`--metrics-out` / `--trace`); this closes the
loop ACROSS runs. Two subcommands, both pure stdlib (no jax import — the
entrypoint must work in an interp-only environment and is smoke-tested
against import rot):

  report FILE            render one artifact as a human phase/level
                         breakdown (phases table, level rollup,
                         throughput, compile/watchdog highlights)
  diff FILE FILE [...]   ingest 2+ artifacts — `--metrics-out` JSONs
                         and/or the BENCH_r*.json family — and emit a
                         trajectory table with regression flags:
                         states/sec drops, phase wall blowups, backend
                         demotions (tpu -> cpu -> interp). With
                         --fail-on-regress the exit status is 1 when
                         any flag fired, so the bench driver can gate.
  timeline FILE [...]    merge multi-process trace JSONLs (daemon +
                         device owner + per-job recorders) into one
                         causally-ordered per-process-lane view;
                         orphan spans and silent gaps are flagged and
                         counted on a machine-parseable summary line
                         (obs/timeline.py; --fail-on-orphans gates).
  top FILE               per-dispatch-site device profile of one
                         --profile artifact: wall, share of the
                         search wall, dispatches, bytes, recompiles,
                         plus the measured device peak and compile
                         seconds per program (obs/prof.py).
  history [...]          per-rung states/sec trajectory across ALL
                         ledger-recorded runs, latest-vs-best-of-
                         window regression flags with env attribution
                         (obs/ledger.py; --fail-on-regress gates,
                         --import backfills committed artifacts).

Both input shapes normalize into one record (`load_record`):
  - a metrics artifact (schema jaxmc.metrics/1 or /2, obs/schema.py);
  - a bench rollup {n, cmd, rc, tail, parsed:{metric, value, ...}} or a
    bare bench line {metric, value, unit, vs_baseline, orchestration?}
    as printed by bench.py.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional

# platform rank for demotion flags: higher is better; a later run with a
# lower rank means the bench/check fell off its accelerator
_RANK = {"interp": 0, "cpu": 1, "gpu": 2, "tpu": 3}


def _fmt_s(x) -> str:
    if x is None:
        return "-"
    if x >= 100:
        return f"{x:.0f}s"
    return f"{x:.2f}s"


def _fmt_rate(x) -> str:
    return "-" if x is None else f"{x:,.1f}"


def _pct(new, old) -> Optional[float]:
    if new is None or old is None or old == 0:
        return None
    return (new - old) / old * 100.0


# --------------------------------------------------------------- loading

def load_record(path: str) -> Dict[str, Any]:
    """Normalize one artifact file into the common record the table and
    the regression rules consume. Raises ValueError on unrecognized
    shapes (naming the path)."""
    with open(path, encoding="utf-8") as fh:
        obj = json.load(fh)
    if not isinstance(obj, dict):
        raise ValueError(f"{path}: not a JSON object")
    label = os.path.basename(path)
    for ext in (".json", ".jsonl"):
        if label.endswith(ext):
            label = label[:-len(ext)]
    if "schema" in obj and "phases" in obj:
        return _from_metrics(obj, path, label)
    if "parsed" in obj and isinstance(obj["parsed"], dict):
        rec = _from_bench(obj["parsed"], path, label)
        if obj.get("n") is not None:
            rec["label"] = f"r{int(obj['n']):02d}"
        return rec
    if "metric" in obj and "value" in obj:
        return _from_bench(obj, path, label)
    raise ValueError(
        f"{path}: neither a jaxmc.metrics artifact nor a bench JSON "
        f"(keys: {sorted(obj)[:8]})")


def _from_metrics(s: Dict[str, Any], path: str, label: str
                  ) -> Dict[str, Any]:
    res = s.get("result") or {}
    wall = res.get("wall_s") or s.get("wall_s")
    gen = res.get("generated")
    rate = (gen / wall) if gen and wall else None
    env = s.get("env") or {}
    platform = env.get("platform") or s.get("gauges", {}).get(
        "device.platform")
    backend = s.get("backend")
    if backend == "interp" or (backend is None and platform is None):
        plat_key = "interp"
    else:
        plat_key = platform or "cpu"
    return {
        "path": path, "label": label, "kind": "metrics",
        "states_per_sec": rate,
        "backend": backend or "?",
        "platform": plat_key,
        "rank": _RANK.get(plat_key, 1),
        # a terminal device failure that completed on the CPU fallback
        # (session.demote_to_cpu); find_regressions flags its appearance
        "demoted": s.get("gauges", {}).get("device.demoted"),
        # a disk-tier write failure that degraded the seen-set
        # hierarchy to host-tier-only (ISSUE 12): counts stayed exact,
        # but the out-of-core ceiling shrank — flagged like a demotion
        "io_degraded": s.get("gauges", {}).get("tier.io_degraded"),
        # fleet-serving reliability signals (ISSUE 19): rejections or
        # spool write degradation appearing where a previous run had
        # none is a serving regression even when every accepted job
        # still completed — flagged like the tier degradation above
        "admission_rejected": s.get("counters", {}).get(
            "serve.admission_rejected"),
        "spool_degraded": s.get("counters", {}).get(
            "serve.spool_degraded"),
        "mode": s.get("gauges", {}).get("expand.mode"),
        "wall_s": s.get("wall_s"),
        "phases": {p["name"]: p["wall_s"] for p in s.get("phases", [])},
        "env": env,
        "result": res,
        "summary": s,
    }


def _from_bench(b: Dict[str, Any], path: str, label: str
                ) -> Dict[str, Any]:
    metric = str(b.get("metric") or "")
    if "EXACT PYTHON INTERPRETER" in metric:
        plat_key = "interp"
    else:
        m = re.search(r"platform=(\w+)", metric)
        plat_key = m.group(1) if m else "interp"
    phases: Dict[str, float] = {}
    for src in (b.get("phases"),
                (b.get("orchestration") or {}).get("phases")):
        for p in src or []:
            phases[p["name"]] = phases.get(p["name"], 0.0) + p["wall_s"]
    orch = b.get("orchestration") or {}
    return {
        "path": path, "label": label, "kind": "bench",
        "states_per_sec": b.get("value"),
        "backend": "bench",
        "platform": plat_key,
        "rank": _RANK.get(plat_key, 1),
        "mode": None,
        "wall_s": orch.get("spent_s"),
        "phases": phases,
        "env": b.get("env") or {},
        "result": {"vs_baseline": b.get("vs_baseline"),
                   "vs_tlc_estimate": b.get("vs_tlc_estimate")},
        "metric": metric,
    }


# ---------------------------------------------------------------- report

def _phase_table(phases: List[Dict[str, Any]], out) -> int:
    """Render a summary's phase list; returns the number of rows."""
    if not phases:
        print("  (no phases recorded)", file=out)
        return 0
    w = max(len(p["name"]) for p in phases)
    total = sum(p["wall_s"] for p in phases)
    for p in phases:
        share = (p["wall_s"] / total * 100.0) if total else 0.0
        flags = "  OPEN" if p.get("open") else ""
        print(f"  {p['name']:<{w}}  {p['wall_s']:>9.3f}s  "
              f"x{p['count']:<4d} {share:5.1f}%{flags}", file=out)
    return len(phases)


def _searches_table(requests: List[Dict[str, Any]], out) -> None:
    """The search records (`requests`, obs/telemetry.py): how many, the
    median and the largest wall, and for the slowest search the piece —
    a child span's wall or one of the host-seconds counters — that grew
    most over its median in the others."""
    if not requests:
        return

    def _s4(x) -> str:  # searches are sub-second: _fmt_s rounds to 0.01
        return f"{x:.4f}s"

    walls = sorted(r["wall_s"] for r in requests)
    median = walls[len(walls) // 2]
    slow = max(requests, key=lambda r: r["wall_s"])
    origins: Dict[str, int] = {}
    for r in requests:
        for o, n in (r.get("origins") or {}).items():
            origins[o] = origins.get(o, 0) + n
    print(f"searches: {len(requests)} records; wall median "
          f"{_s4(median)}, largest {_s4(slow['wall_s'])} "
          f"(rid {slow['rid']}); dispatches by origin {origins}",
          file=out)
    if len(requests) < 2 or slow["wall_s"] <= median:
        return

    def pieces(r):
        return {**(r.get("spans") or {}), **(r.get("counters") or {})}

    def rise_of(get):
        vals = sorted(get(r) for r in requests if r is not slow)
        return get(slow) - vals[len(vals) // 2]

    rise, name = max((rise_of(lambda r, n=n: pieces(r).get(n, 0.0)), n)
                     for n in pieces(slow))
    late = slow["wall_s"] - median
    cpu = rise_of(lambda r: r.get("cpu_s", 0.0))
    print(f"  slowest search +{_s4(late)} over the median: {name} grew "
          f"most, +{_s4(rise)} to {_s4(pieces(slow)[name])}; its CPU "
          f"seconds {cpu:+.4f}s"
          + ("" if cpu > 0.5 * late else " (the process was not running)"),
          file=out)


def cmd_report(args, out=sys.stdout) -> int:
    rec = load_record(args.file)
    print(f"== {rec['label']} ({rec['kind']}: {args.file})", file=out)
    env = rec["env"]
    bits = [f"backend={rec['backend']}", f"platform={rec['platform']}"]
    if rec["mode"]:
        bits.append(f"mode={rec['mode']}")
    if env.get("jax_version"):
        bits.append(f"jax={env['jax_version']}")
    if env.get("device_count"):
        bits.append(f"devices={env['device_count']}")
    print("  " + "  ".join(bits), file=out)
    if rec["kind"] == "bench":
        print(f"  states/sec: {_fmt_rate(rec['states_per_sec'])}  "
              f"vs_baseline={rec['result'].get('vs_baseline')}  "
              f"vs_tlc_estimate={rec['result'].get('vs_tlc_estimate')}",
              file=out)
        print("phases (child + orchestration):", file=out)
        _phase_table(
            [{"name": k, "wall_s": v, "count": 1}
             for k, v in rec["phases"].items()], out)
        # pre-PR1 bench lines carry no phases — that is a fact about the
        # artifact, not a rendering failure
        return 0
    s = rec["summary"]
    res = rec["result"]
    if res:
        print(f"  result: ok={res.get('ok')}  "
              f"distinct={res.get('distinct')}  "
              f"generated={res.get('generated')}  "
              f"diameter={res.get('diameter')}  "
              f"truncated={res.get('truncated')}", file=out)
        print(f"  throughput: {_fmt_rate(rec['states_per_sec'])} "
              f"states/sec over {_fmt_s(res.get('wall_s'))} search "
              f"({_fmt_s(s.get('wall_s'))} total)", file=out)
    print("phases:", file=out)
    rows = _phase_table(s.get("phases", []), out)
    levels = s.get("levels", [])
    if levels:
        gen = sum(r.get("generated", 0) for r in levels)
        walls = [r["wall_s"] for r in levels
                 if isinstance(r.get("wall_s"), (int, float))]
        print(f"levels: {len(levels)} records to depth "
              f"{levels[-1]['level']}; {gen} generated; "
              f"slowest level {_fmt_s(max(walls) if walls else None)}",
              file=out)
    hl = []
    c, g = s.get("counters", {}), s.get("gauges", {})
    for k in ("compile.kernels_built", "compile.cache_hits",
              "compile.cache_misses", "compile.jaxpr_eqns_total",
              "compile.hlo_flops_total", "watchdog.stalls",
              "mesh.host_syncs", "mesh.row_syncs",
              "mesh.exchange_bytes", "analyze.predicted_demotions",
              "analyze.lint_diags", "tier.spills",
              "tier.spilled_keys", "tier.compactions"):
        if k in c:
            hl.append(f"{k}={c[k]}")
    # out-of-core highlight row (ISSUE 12): one cell naming each tier's
    # key occupancy, so a spilling run's artifact reads
    # tier[device=… host=… disk=…] at a glance
    occ = g.get("tier.occupancy")
    if isinstance(occ, dict):
        hl.append("tier[" + " ".join(
            f"{t}={occ.get(t, 0)}" for t in ("device", "host", "disk"))
            + "]")
    # cross-model batching highlight row (ISSUE 13): cohort width,
    # dispatch count and the constants riding the batch axis — a
    # batched fleet artifact reads batch[occupancy=4 dispatches=40
    # lifted=Bound,Limit] at a glance
    bocc = g.get("batch.occupancy", g.get("serve.batch_occupancy"))
    if isinstance(bocc, int) and bocc:
        cells = [f"occupancy={bocc}"]
        bd = g.get("batch.dispatch_count")
        if isinstance(bd, int):
            cells.append(f"dispatches={bd}")
        lifted = g.get("batch.lifted_consts")
        if isinstance(lifted, list) and lifted:
            cells.append("lifted=" + ",".join(str(x) for x in lifted))
        fl = c.get("serve.fastlane_jobs")
        if fl:
            cells.append(f"fastlane={fl}")
        hl.append("batch[" + " ".join(cells) + "]")
    # proven-lane ratio (ISSUE 9): how much of the int-lane surface the
    # static analyzer proved vs what stayed sampled+guarded
    pv, gd = g.get("analyze.proven_lanes"), \
        g.get("layout.pack_guarded_lanes")
    if isinstance(pv, int) and isinstance(gd, int) and (pv or gd):
        hl.append(f"analyze.proven_lanes={pv}/{pv + gd} "
                  f"({100.0 * pv / (pv + gd):.0f}% of int lanes "
                  f"proven)")
    for k in ("expand.mode", "dedup.mode", "seen.mode",
              "tier.device_cap", "tier.cap_breached",
              "tier.probe_wall_s",
              "tier.io_degraded", "truncation.reason",
              "fingerprint.collision_p",
              "layout.width_lanes",
              "layout.packed_width_lanes", "layout.bits_per_state",
              "device.donation", "profile.status",
              "fingerprint.occupancy", "mesh.exchange", "mesh.devices",
              "mesh.compact_form", "mesh.finish_form",
              "mesh.supersteps", "mesh.superstep_levels",
              "mesh.a2a_gamma", "mesh.a2a_spill", "mesh.a2a_max_bucket",
              "mesh.shard_balance",
              "backend.oracle_choice", "backend.oracle_wall_s",
              "device.mem_high_water_bytes", "watchdog.max_stall_s"):
        if k in g:
            hl.append(f"{k}={g[k]}")
    # preflight oracle probes (ISSUE 11 satellite): one cell per
    # candidate platform — live probes show their dispatch wall, dead
    # ones the first words of why
    op = g.get("backend.oracle_probe")
    if isinstance(op, dict):
        cells = []
        for plat, pr in op.items():
            if isinstance(pr, dict) and pr.get("live"):
                cells.append(f"{plat}={pr.get('dispatch_s')}s")
            else:
                why = (pr or {}).get("error", "?") \
                    if isinstance(pr, dict) else "?"
                cells.append(f"{plat}=dead({str(why)[:40]})")
        hl.append("backend.oracle_probe[" + " ".join(cells) + "]")
    # fleet-serve highlight row (PR 16): how the daemon ran this job —
    # serve[warm=yes resumed=yes recompiles=0 batched_with=2] at a
    # glance, same keys cmd_smoke asserts on
    sv = s.get("serve")
    if isinstance(sv, dict) and sv:
        cells = []
        if "warm_engine" in sv:
            cells.append(f"warm={'yes' if sv['warm_engine'] else 'no'}")
        if "resumed_from_checkpoint" in sv:
            cells.append("resumed=" + (
                "yes" if sv["resumed_from_checkpoint"] else "no"))
        if "window_recompiles" in sv:
            cells.append(f"recompiles={sv['window_recompiles']}")
        if sv.get("program_hits"):
            cells.append(f"held_programs={sv['program_hits']}")
        bw = sv.get("batched_with")
        if isinstance(bw, list) and bw:
            cells.append(f"batched_with={len(bw)}")
        if sv.get("cost_estimate") is not None:
            cells.append(f"est={sv['cost_estimate']}")
        if sv.get("job_wall_s") is not None:
            cells.append(f"wall={_fmt_s(sv['job_wall_s'])}")
        if cells:
            hl.append("serve[" + " ".join(cells) + "]")
    if hl:
        print("highlights: " + "  ".join(hl), file=out)
    # the program registry (ISSUE 37, compile/cache.py): how many of
    # the programs this run's engines asked for their process held
    held, made, unkeyed = (c.get(f"compile.program_{k}", 0)
                           for k in ("hits", "misses", "unkeyed"))
    if held or made or unkeyed:
        print(f"programs: {held} held by the process (no trace, no "
              f"load), {made} made new, {unkeyed} unkeyed (no program "
              f"signature); signing took "
              f"{c.get('compile.program_sig_s', 0.0):.4f}s", file=out)
    _cohort_lines(s, out)
    _stations_line(s, out)
    _searches_table(s.get("requests") or [], out)
    return 0 if rows else 1


def _stations_line(s: Dict[str, Any], out) -> None:
    """Where a SERVED job's wall went between the POST's record and the
    verdict's (ISSUE 49, serve/protocol.py "A job's clock"): one
    `stations:` line from the `serve` block — the queue (record made ->
    a worker's claim), the wait for the device owner, the owner's
    envelope (pipe, config, summary, pickling: everything of the
    request that is not the run), the run where it ran and the
    publish (the artifact's write).  A job no owner ran has the queue
    and the whole alone."""
    sv = s.get("serve")
    st = sv.get("stations") if isinstance(sv, dict) else None
    if not isinstance(st, dict) or "claimed_at" not in st \
            or "submitted_at" not in st:
        return

    def _ms(x) -> str:  # stations are milliseconds apart: _fmt_s
        return "-" if x is None else f"{x:.3f}s"  # rounds to 0.01
    cells = [f"queue {_ms(st['claimed_at'] - st['submitted_at'])}"]
    if "owner_sent_at" in st:
        cells += [f"owner wait {_ms(sv.get('owner_wait_s'))}",
                  f"envelope {_ms(sv.get('owner_envelope_s'))}"
                  + (f" (the owner spawned: "
                     f"{_ms(sv['owner_spawn_s'])} coming up)"
                     if sv.get("owner_spawn_s") is not None else ""),
                  f"run {_ms(sv.get('job_wall_s'))}",
                  f"publish {_ms(sv.get('publish_s'))}"]
    else:
        cells.append("no owner station (the job ran in the daemon)")
    if "finished_at" in st:
        cells.append(
            f"record to record "
            f"{_ms(st['finished_at'] - st['submitted_at'])}")
    print("stations: " + " · ".join(cells), file=out)


def _cohort_lines(s: Dict[str, Any], out) -> None:
    """Where a vmapped cohort's wall went (ISSUE 39, backend/batch.py):
    a `cohort:` line from the leader's artifact (the build, the first
    call of the vmapped program, the run and what its supersteps cost
    the host) and a `host_seen:` line from any member's, a solo
    `--host-seen` run included (the host's side of its levels)."""
    c = s.get("counters", {})
    ph = {p["name"]: p["wall_s"] for p in s.get("phases", [])}
    n = c.get("batch.dispatches")
    if n:
        fire = ph.get("batch.dispatch", 0.0) + \
            c.get("batch.stack_s", 0.0) + c.get("batch.unstack_s", 0.0)
        # what came back (ISSUE 40): one transfer a dispatch
        back = (f" in {c['batch.fetch_transfers']} transfers, "
                f"{c.get('batch.fetch_mb', 0.0):.1f} MB"
                if "batch.fetch_transfers" in c else "")
        print(f"cohort: build {_fmt_s(ph.get('batch.build'))}, first "
              f"call {_fmt_s(c.get('batch.first_dispatch_s'))}, run "
              f"{_fmt_s(ph.get('batch.run'))}; {n} dispatches, "
              f"{c.get('batch.lane_steps', 0)} member lanes filled; "
              f"firing them {_fmt_s(fire)} (stack "
              f"{_fmt_s(c.get('batch.stack_s'))}, upload "
              f"{_fmt_s(c.get('batch.upload_s'))}, fetch "
              f"{_fmt_s(c.get('batch.fetch_s'))}{back}, unstack "
              f"{_fmt_s(c.get('batch.unstack_s'))})", file=out)
    if c.get("hostseen.chunks"):
        wait = c.get("batch.barrier_wait_s")
        print(f"host_seen: {c['hostseen.chunks']} chunks; step "
              f"{_fmt_s(c.get('hostseen.step_s'))}"
              + (f" (barrier wait {_fmt_s(wait)})"
                 if wait is not None else "")
              + f", store {_fmt_s(c.get('hostseen.store_s'))} for "
              f"{c.get('hostseen.store_keys', 0)} keys, bookkeeping "
              f"{_fmt_s(c.get('hostseen.book_s'))}, level tails "
              f"{_fmt_s(c.get('hostseen.tail_s'))}", file=out)


# ------------------------------------------------------------------ diff

def _effective_env(rec: Dict[str, Any]) -> Dict[str, Any]:
    """The record's env dict with platform/device_count backfilled from
    the record itself (ISSUE 11 satellite): metrics artifacts written
    by interp runs leave env.platform None, so a backend swap between two
    artifacts used to surface as an unexplained REGRESS instead of an
    attributed environment change."""
    env = dict(rec.get("env") or {})
    if env.get("platform") is None and rec.get("platform"):
        env["platform"] = rec["platform"]
    if env.get("device_count") is None:
        g = (rec.get("summary") or {}).get("gauges") or {}
        dc = g.get("mesh.devices") or g.get("device.count")
        if dc is not None:
            env["device_count"] = dc
    return env


def _env_changes(a: Dict[str, Any], b: Dict[str, Any]) -> List[str]:
    out = []
    for k in ("jax_version", "platform", "device_count", "python"):
        va, vb = a.get(k), b.get(k)
        if va is not None and vb is not None and va != vb:
            out.append(f"{k}: {va} -> {vb}")
    return out


def find_regressions(prev: Dict[str, Any], cur: Dict[str, Any],
                     threshold_pct: float,
                     ignore_phases: frozenset = frozenset()
                     ) -> List[str]:
    """Regression flags between two consecutive records. Environment
    changes are reported alongside each flag so a demotion caused by a
    jax upgrade (or a lost device) reads as such.  `ignore_phases`
    names phases excluded from the per-phase wall gate (cold-start
    one-shot walls like compile_arm are load-sensitive in a way the
    measured search window is not); the states/sec and demotion
    gates always apply."""
    flags = []
    step = f"{prev['label']} -> {cur['label']}"
    d = _pct(cur["states_per_sec"], prev["states_per_sec"])
    if d is not None and d < -threshold_pct:
        flags.append(
            f"REGRESS states/sec {step}: "
            f"{_fmt_rate(prev['states_per_sec'])} -> "
            f"{_fmt_rate(cur['states_per_sec'])} ({d:+.1f}%)")
    if cur["rank"] < prev["rank"]:
        flags.append(
            f"REGRESS backend demotion {step}: {prev['platform']} -> "
            f"{cur['platform']}")
    if cur.get("demoted") and not prev.get("demoted"):
        # the run finished (counts are exact via the CPU fallback) but
        # the device path died mid-run — a reliability regression even
        # when the rates happen to survive
        flags.append(
            f"REGRESS device demotion {step}: device backend failed "
            f"terminally, run completed on the CPU fallback "
            f"({cur['demoted']})")
    if cur.get("io_degraded") and not prev.get("io_degraded"):
        # counts stayed exact (the store fell back to host-tier-only)
        # but the disk tier died mid-run — the out-of-core capacity
        # ceiling regressed even though the search survived
        flags.append(
            f"REGRESS tier io degradation {step}: disk-tier write "
            f"failed, seen-set hierarchy ran host-tier-only "
            f"({cur['io_degraded']})")
    if cur.get("admission_rejected") and \
            not prev.get("admission_rejected"):
        # accepted jobs completed, but the fleet turned clients away —
        # capacity (or a tenant budget) regressed vs the previous run
        flags.append(
            f"REGRESS serve admission rejections {step}: "
            f"{cur['admission_rejected']} submissions refused with 429 "
            f"where the previous run refused none")
    if cur.get("spool_degraded") and not prev.get("spool_degraded"):
        # the durable spool exhausted its write retries: results kept
        # flowing over HTTP but durability (restart recovery, takeover)
        # regressed for the affected records
        flags.append(
            f"REGRESS serve spool degradation {step}: spool writes "
            f"exhausted their retries ({cur['spool_degraded']} "
            f"degradation events)")
    for name in sorted(set(prev["phases"]) & set(cur["phases"])):
        if name in ignore_phases:
            continue
        pw, cw = prev["phases"][name], cur["phases"][name]
        pd = _pct(cw, pw)
        # absolute floor: a 3 ms parse doubling is noise, not a flag
        if pd is not None and pd > threshold_pct and cw - pw > 1.0:
            flags.append(
                f"REGRESS phase {name} {step}: {_fmt_s(pw)} -> "
                f"{_fmt_s(cw)} ({pd:+.1f}%)")
    if flags:
        env = _env_changes(_effective_env(prev), _effective_env(cur))
        if env:
            flags.append(f"  note {step}: environment changed "
                         f"({'; '.join(env)})")
    return flags


def _record_ts(rec: Dict[str, Any]) -> float:
    """The record's recorded timestamp for trajectory ordering:
    metrics artifacts carry started_at; bench rollups do not, so the
    file mtime stands in."""
    s = rec.get("summary") or {}
    ts = s.get("started_at")
    if isinstance(ts, (int, float)):
        return float(ts)
    try:
        return os.path.getmtime(rec["path"])
    except OSError:
        return 0.0


def expand_artifact_args(paths: List[str]) -> List[str]:
    """`obs diff` input expansion (ISSUE 17 satellite): each argument
    may be a file, a glob, or a directory (-> its *.json files).  When
    ANY argument expanded, the caller re-orders the whole set by
    recorded timestamp — a shell-quoted "BENCH_r*.json" must diff in
    run order, not lexical luck."""
    out: List[str] = []
    expanded = False
    for p in paths:
        if os.path.isdir(p):
            import glob as _glob
            out.extend(sorted(_glob.glob(os.path.join(p, "*.json"))))
            expanded = True
        elif any(ch in p for ch in "*?["):
            import glob as _glob
            hits = sorted(_glob.glob(p))
            if not hits:
                raise ValueError(f"{p}: glob matched no files")
            out.extend(hits)
            expanded = True
        else:
            out.append(p)
    if not expanded:
        return paths  # explicit files pass through — `diff A A` is legal
    # dedup while preserving order (a dir + an explicit member)
    seen = set()
    return [p for p in out if not (p in seen or seen.add(p))]


def cmd_diff(args, out=sys.stdout) -> int:
    files = expand_artifact_args(args.files)
    recs = [load_record(p) for p in files]
    if files != args.files:
        # expansion happened: order the trajectory by recorded
        # timestamp instead of trusting the shell's lexical order
        recs.sort(key=_record_ts)
    if len(recs) < 2:
        print("error: diff needs at least two artifacts",
              file=sys.stderr)
        return 2
    # trajectory table: one row per run, the shared top phases as columns
    phase_tot: Dict[str, float] = {}
    for r in recs:
        for k, v in r["phases"].items():
            phase_tot[k] = phase_tot.get(k, 0.0) + v
    cols = [k for k, _ in sorted(phase_tot.items(),
                                 key=lambda kv: -kv[1])[:5]]
    lw = max([5] + [len(r["label"]) for r in recs])
    head = (f"{'run':<{lw}}  {'states/sec':>12}  {'platform':>8}  "
            + "  ".join(f"{c:>14}" for c in cols))
    print(head, file=out)
    print("-" * len(head), file=out)
    for r in recs:
        cells = "  ".join(
            f"{_fmt_s(r['phases'].get(c)):>14}" for c in cols)
        print(f"{r['label']:<{lw}}  "
              f"{_fmt_rate(r['states_per_sec']):>12}  "
              f"{r['platform']:>8}  {cells}", file=out)
    ignore = frozenset(
        p for p in (args.ignore_phases or "").split(",") if p)
    flags: List[str] = []
    for prev, cur in zip(recs, recs[1:]):
        flags.extend(find_regressions(prev, cur, args.threshold,
                                      ignore_phases=ignore))
    print("", file=out)
    if flags:
        print("regressions:", file=out)
        for f in flags:
            print(f"  {f}", file=out)
    else:
        print("no regressions flagged "
              f"(threshold {args.threshold:.0f}%).", file=out)
    real = [f for f in flags if f.lstrip().startswith("REGRESS")]
    if real and args.fail_on_regress:
        return 1
    return 0


# ------------------------------------------------------------------ main

def main(argv: Optional[List[str]] = None, out=sys.stdout) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m jaxmc.obs",
        description="render and compare jaxmc metrics artifacts")
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("report", help="render one metrics/bench artifact")
    r.add_argument("file")
    d = sub.add_parser("diff",
                       help="trajectory table + regression flags over "
                            "2+ metrics/bench artifacts (files, "
                            "quoted globs, or directories — expanded "
                            "and ordered by recorded timestamp)")
    d.add_argument("files", nargs="+")
    d.add_argument("--threshold", type=float, default=10.0,
                   metavar="PCT",
                   help="relative change that counts as a regression "
                        "(default 10%%; phase flags also need >1s "
                        "absolute growth)")
    d.add_argument("--fail-on-regress", action="store_true",
                   help="exit 1 when any REGRESS flag fired (bench/CI "
                        "gate)")
    d.add_argument("--ignore-phases", default="", metavar="P1,P2",
                   help="comma-separated phase names excluded from "
                        "the per-phase wall gate (cold-start compile "
                        "walls flap with box load; states/sec and "
                        "demotion gates always apply)")
    t = sub.add_parser(
        "timeline",
        help="merge multi-process trace JSONLs into one causally "
             "ordered per-process-lane view (orphan spans + silent "
             "gaps flagged)")
    t.add_argument("files", nargs="+")
    t.add_argument("--limit", type=int, default=200,
                   help="max merged events to print (0 = all; the "
                        "summary line always counts all)")
    t.add_argument("--gap-threshold", type=float, default=30.0,
                   metavar="SECONDS",
                   help="flag a lane silent for longer than this "
                        "(default 30s)")
    t.add_argument("--fail-on-orphans", action="store_true",
                   help="exit 1 when any lane's parent span resolves "
                        "to no known process")
    tp = sub.add_parser(
        "top",
        help="per-dispatch-site profile table (wall, share, "
             "dispatches, bytes, recompiles), the measured device peak "
             "and compile seconds per program from one "
             "--profile metrics artifact (jaxmc.metrics/4 prof{})")
    tp.add_argument("file")
    h = sub.add_parser(
        "history",
        help="per-rung states/sec trajectory across ALL ledger-"
             "recorded runs; flags the latest run per rung against "
             "the rolling best-of-window")
    h.add_argument("--ledger", default=None, metavar="FILE",
                   help="ledger JSONL (default: JAXMC_LEDGER or "
                        "~/.cache/jaxmc/ledger.jsonl)")
    h.add_argument("--rung", default=None,
                   help="restrict to one rung (e.g. transfer_scaled)")
    h.add_argument("--import", dest="import_files", nargs="+",
                   default=None, metavar="ARTIFACT",
                   help="backfill artifacts (BENCH_r*.json, "
                        "--metrics-out JSONs; "
                        "globs ok) into the ledger first — "
                        "content-addressed, so re-importing is "
                        "idempotent")
    h.add_argument("--threshold", type=float, default=25.0,
                   metavar="PCT",
                   help="relative drop vs best-of-window that counts "
                        "as a regression (default 25%%; ledger points "
                        "span machines and months, so the bar is "
                        "looser than diff's pairwise 10%%)")
    h.add_argument("--window", type=int, default=5,
                   help="how many preceding runs form the rolling "
                        "best-of reference (default 5)")
    h.add_argument("--fail-on-regress", action="store_true",
                   help="exit 1 when the latest run of any rendered "
                        "rung regressed")
    args = ap.parse_args(argv)
    try:
        if args.cmd == "report":
            return cmd_report(args, out)
        if args.cmd == "timeline":
            from .timeline import cmd_timeline
            return cmd_timeline(args, out)
        if args.cmd == "top":
            from .prof import cmd_top
            return cmd_top(args, out)
        if args.cmd == "history":
            from .ledger import cmd_history
            return cmd_history(args, out)
        return cmd_diff(args, out)
    except (OSError, ValueError, KeyError) as e:
        print(f"error: {type(e).__name__}: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
