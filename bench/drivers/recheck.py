"""Driver for the `recheck` kind of traffic: whole exhaustive searches on one
built engine in THIS process (which therefore holds the chip), one after
another, until the window has passed.  jax is imported here, after the
arguments are parsed, never by run.py.

Set-up (counted in `setup_s`): write the seed's cfg, jax and chip init,
parse, kernel build, one warm-up search (it compiles, or loads from the
checkout's cache, every program the window drives).  Window: `explore()`
again and again on the same engine — the very object the warm-up drove.
"""

from __future__ import annotations

import os
import sys
import time

from lib import (BenchFailure, check_pins, compare, need, reference_answer,
                 say, work_dir, write_seed_cfg)


def _result_dict(res, sess) -> dict:
    return {"generated": res.generated, "distinct": res.distinct,
            "diameter": res.diameter, "ok": bool(res.ok),
            "truncated": bool(getattr(res, "truncated", False)),
            "finished_on": sess.finished_on,
            "seen_mode": getattr(res, "seen_mode", None)}


def _counters(tel) -> dict:
    snap = tel.metrics_snapshot()
    sites = {n: s.as_dict() for n, s in tel.prof.sites.items()}
    return {"counters": snap["counters"], "gauges": snap["gauges"],
            "fresh_compiles": sum(1 for lv in snap["levels"]
                                  if lv.get("fresh_compile")),
            "dispatches": {n: s.get("dispatches", 0)
                           for n, s in sites.items()},
            "phases": {p["name"]: p["wall_s"] for p in tel.phase_list()}}


def run(ctx: dict) -> dict:
    mix, pins, root = ctx["mix"], ctx["pins"], ctx["root"]
    rehearsal, trace = ctx["rehearsal"], ctx["trace"]
    platform = "cpu" if rehearsal else ctx["config"]["session"]["platform"]
    work = work_dir(ctx["cell"]["name"], root)
    cfg_text, cfg_path = write_seed_cfg(ctx, work)

    os.environ.setdefault("JAXMC_LEDGER", "off")
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, root)
    import jax
    from jaxmc import obs
    from jaxmc.session import CheckSession, SessionConfig

    opts = dict(ctx["config"]["session"], **mix["session"])
    opts["platform"] = platform
    if mix.get("use_pinned_caps") and not rehearsal:
        opts["res_caps"] = dict(pins["res_caps"])
    tel = obs.Telemetry(meta={"command": "bench.recheck",
                              "workload": ctx["cell"]["name"]})
    with obs.use(tel):
        sess = CheckSession(SessionConfig(
            spec=os.path.join(root, mix["spec"]), cfg=cfg_path, **opts),
            tel=tel)
        try:
            sess.compile()
        except Exception as ex:  # noqa: BLE001 — no chip, no result
            raise BenchFailure(f"engine did not come up on {platform!r}: "
                               f"{type(ex).__name__}: {ex}") from ex
        devs = jax.devices()
        need(devs[0].platform == platform,
             f"jax initialized {devs[0].platform!r}, not {platform!r}")
        need(len(devs) >= ctx["cell"]["chips"],
             f"{len(devs)} device(s), the cell asks for "
             f"{ctx['cell']['chips']}")
        # ---- warm-up: every program of the window, on the same engine
        with tel.span("bench.warmup"):
            warm = _result_dict(sess.explore(), sess)
        at_window = _counters(tel)

        # ---- the window
        seconds = ctx["seconds"]
        traced = mix.get("trace_searches", 1) if trace else 0
        trace_dir = os.path.join(work, "trace")
        searches = []

        def one_search():
            with jax.profiler.TraceAnnotation("bench.search"):
                t = time.perf_counter()
                res = sess.explore()
            searches.append((time.perf_counter() - t,
                             _result_dict(res, sess)))

        t_window = time.time()
        setup_s = t_window - ctx["t0"]
        w0 = time.perf_counter()
        if traced:
            # a traced run's window is the traced searches and no more:
            # traces are large and the rate is taken with tracing off
            opt = jax.profiler.ProfileOptions()
            opt.python_tracer_level = 0
            opt.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opt)
            with jax.profiler.TraceAnnotation("bench.window"):
                while len(searches) < traced:
                    one_search()
            jax.profiler.stop_trace()
        else:
            while not searches or time.perf_counter() - w0 < seconds:
                one_search()
        window_wall = time.perf_counter() - w0
        after = _counters(tel)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs[:ctx["cell"]["chips"]]) if not rehearsal \
            else 0
        demoted = after["gauges"].get("device.demoted") or \
            after["counters"].get("device.demotions")
    tel.close()
    need(not demoted, f"the run DEMOTED off the device: {demoted}")
    for _, got in [(0, warm)] + searches:
        need(got["finished_on"] == "jax",
             f"a search finished on {got['finished_on']!r}, not the device")

    # ---- correct: after the window, outside set-up
    t_ref = time.perf_counter()
    ref = reference_answer(mix, cfg_text, ctx["bench_dir"])
    ref_s = time.perf_counter() - t_ref
    if not rehearsal:
        check_pins(ref, pins)
    say(f"bench: plain reference {ref['generated']} generated / "
        f"{ref['distinct']} distinct / diameter {ref['diameter']} "
        f"in {ref_s:.2f}s")
    warm_ok = compare(warm, ref, "warm-up")
    failed = sum(0 if compare(got, ref, f"search[{i}]") else 1
                 for i, (_, got) in enumerate(searches))
    rate = sum(g["generated"] for _, g in searches) / window_wall
    say(f"bench: {len(searches)} search(es) in {window_wall:.3f}s"
        f"{' (traced)' if traced else ''}; search walls "
        f"{[round(dt, 3) for dt, _ in searches]}")
    return {
        "attempted": len(searches), "failed": failed,
        "correct": warm_ok and failed == 0,
        "values": {"states_per_s": rate, "setup_s": setup_s},
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs),
                   "memory_peak_bytes": int(peak)},
        "trace_dir": trace_dir if traced else None,
        "artifacts": {"at_window": at_window, "after": after,
                      "searches": len(searches), "reference": ref,
                      "seen_mode": warm["seen_mode"]},
    }
