"""Driver for the `symmetry` kind of traffic: whole exhaustive searches of a
cfg with a `SYMMETRY` line, on one built engine in THIS process (which
therefore holds the chip), one after another, until the window has passed.
The `recheck` driver's shape (its counters and result fields are its own
functions, loaded from its file), with the one thing `recheck` never asks:
whether the reduction RAN ON THE DEVICE.

An engine that cannot canonicalise the cfg's group on the device falls back
to the UNREDUCED search with a warning (`bfs._symmetry_warnings`): at five
processes that is a model of 579 M states, which under pinned capacities
would climb the capacity ladder for minutes and never fit.  So RIGHT AFTER
the build and BEFORE the warm-up search the run ends, with no result, unless
the engine says it applies the cfg's SYMMETRY on the device in the form the
mix names (`symmetry_form`: the program before PR 47 says nothing, or holds
no canonicaliser at this group order).

Set-up (counted in `setup_s`): write the seed's cfg, jax and chip init,
parse, kernel build (the initial states are canonicalised here, on the
host's side of the same function), one warm-up search.  Window: `explore()`
again and again on the same engine.  `states_per_s` = the `generated` of the
window's searches / the window's wall.

`correct`: every search's counts, verdict and `truncated` equal the plain
reference's under TLC's counting with symmetry (`lib.compare`, limit 0).
"""

from __future__ import annotations

import os
import sys
import time

from lib import (BenchFailure, check_pins, compare, load_module, need,
                 reference_answer, say, work_dir, write_seed_cfg)


def run(ctx: dict) -> dict:
    mix, pins, root = ctx["mix"], ctx["pins"], ctx["root"]
    rehearsal, trace = ctx["rehearsal"], ctx["trace"]
    recheck = load_module(os.path.join(ctx["bench_dir"], "drivers",
                                       "recheck.py"), "bench_driver_recheck")
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
    tel = obs.Telemetry(meta={"command": "bench.symmetry",
                              "workload": ctx["cell"]["name"]})

    def search():
        return recheck._result_dict(sess.explore(), sess)

    with obs.use(tel):
        sess = CheckSession(SessionConfig(
            spec=os.path.join(root, mix["spec"]), cfg=cfg_path, **opts),
            tel=tel)
        try:
            sess.compile()
        except Exception as ex:  # noqa: BLE001 — no chip, no result
            raise BenchFailure(f"engine did not come up on {platform!r}: "
                               f"{type(ex).__name__}: {ex}") from ex
        # ---- the guard: before any search
        engine = sess.engine
        form = getattr(engine, "sym_form", None)
        need(getattr(engine, "canon_fn", None) is not None
             and form == mix["symmetry_form"],
             f"the engine does not apply the cfg's SYMMETRY on the device "
             f"in the {mix['symmetry_form']!r} form (form {form!r}, "
             f"fallback {getattr(engine, '_sym_fallback', None)!r}): an "
             f"unreduced search is another model, not a slower one")
        devs = jax.devices()
        need(devs[0].platform == platform,
             f"jax initialized {devs[0].platform!r}, not {platform!r}")
        need(len(devs) >= ctx["cell"]["chips"],
             f"{len(devs)} device(s), the cell asks for "
             f"{ctx['cell']['chips']}")
        # ---- warm-up: every program of the window, on the same engine
        with tel.span("bench.warmup"):
            warm = search()
        at_window = recheck._counters(tel)

        # ---- the window
        seconds = ctx["seconds"]
        traced = mix.get("trace_searches", 1) if trace else 0
        trace_dir = os.path.join(work, "trace")
        searches = []

        def one_search():
            with jax.profiler.TraceAnnotation("bench.search"):
                t = time.perf_counter()
                got = search()
            searches.append((time.perf_counter() - t, got))

        t_window = time.time()
        setup_s = t_window - ctx["t0"]
        w0 = time.perf_counter()
        if traced:
            # a traced run's window is the traced searches and no more
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
        after = recheck._counters(tel)
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
    need(after["gauges"].get("symmetry.form") == mix["symmetry_form"],
         f"the program's gauge symmetry.form reads "
         f"{after['gauges'].get('symmetry.form')!r}")

    # ---- correct: after the window, outside set-up
    t_ref = time.perf_counter()
    ref = reference_answer(mix, cfg_text, ctx["bench_dir"])
    ref_s = time.perf_counter() - t_ref
    if not rehearsal:
        check_pins(ref, pins)
    say(f"bench: plain reference {ref['generated']} generated / "
        f"{ref['distinct']} distinct / diameter {ref['diameter']} "
        f"in {ref_s:.2f}s; the program's group order "
        f"{after['gauges'].get('symmetry.group_order')}, form {form}")
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
                      "seen_mode": warm["seen_mode"],
                      "symmetry_form": form},
    }
