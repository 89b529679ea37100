"""Driver for the `constraint` kind of traffic: whole exhaustive searches of a
cfg whose `CONSTRAINT` alone makes the model finite, on one built engine in
THIS process (which therefore holds the chip), one after another, until the
window has passed.  The `recheck` driver's shape (its counters and result
fields are its own functions, loaded from its file), with the two things
`recheck` never asks: whether the constraint is judged ON THE DEVICE, and
whether every search discarded exactly the rows the reference discards.

An engine that cannot compile a CONSTRAINT hands it to the interpreter on
the host (`engine.fb_cons`, gauge `expand.constraints_interp`): a hybrid
engine, another one, not a slower one — it would pull every level's new
rows over the host boundary.  So RIGHT AFTER the build and BEFORE the
warm-up search the run ends, with no result, unless the engine compiled
every CONSTRAINT of the cfg for the device and says so itself (gauge
`constraint.compiled`: the program before PR 51 says nothing).

Set-up (counted in `setup_s`): write the seed's cfg, jax and chip init,
parse, kernel build, one warm-up search.  Window: `explore()` again and
again on the same engine.  `states_per_s` = the `generated` of the window's
searches / the window's wall.

`correct`: every search's counts, verdict and `truncated` equal the plain
reference's under TLC's counting with a CONSTRAINT (`lib.compare`, limit 0:
`generated` counts a discarded successor, `distinct` does not), AND every
search's `search.rows_discarded` — the rows that entered the seen table and
were kept out of the frontier — equals the reference's `discarded`, limit 0.
"""

from __future__ import annotations

import os
import sys
import time

from lib import (BenchFailure, check_pins, compare, load_module, need,
                 reference_answer, say, work_dir, write_seed_cfg)

DISCARDED = "search.rows_discarded"


def compare_discarded(got: dict, ref: dict, label: str) -> bool:
    """One more number of a search beside its limit, printed as
    `lib.compare` prints the others."""
    g, w = got.get("rows_discarded"), ref["discarded"]
    gap = None if g is None else abs(int(g) - int(w))
    say(f"  compare {label} rows_discarded: program {g} reference {w} "
        f"gap {gap} limit 0 {'ok' if gap == 0 else 'FAILED'}")
    return gap == 0


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
    tel = obs.Telemetry(meta={"command": "bench.constraint",
                              "workload": ctx["cell"]["name"]})

    def search():
        had = tel.counters.get(DISCARDED)
        got = recheck._result_dict(sess.explore(), sess)
        now = tel.counters.get(DISCARDED)
        got["rows_discarded"] = None if now is None else now - (had or 0)
        return got

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
        compiled = [nm for nm, _ in getattr(engine, "constraint_fns", ())]
        interp = [c[0] for c in getattr(engine, "fb_cons", ())]
        gauges = tel.metrics_snapshot()["gauges"]
        need(compiled == list(mix["constraints"]) and not interp
             and gauges.get("expand.constraints_interp") == 0
             and gauges.get("constraint.compiled") == len(compiled),
             f"the engine does not judge the cfg's CONSTRAINT "
             f"{mix['constraints']} on the device (compiled {compiled}, "
             f"interpreted {interp}, gauge constraint.compiled "
             f"{gauges.get('constraint.compiled')!r}, "
             f"expand.constraints_interp "
             f"{gauges.get('expand.constraints_interp')!r}): an interpreter "
             f"fallback is another engine, not a slower one")
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

    # ---- correct: after the window, outside set-up
    t_ref = time.perf_counter()
    ref = reference_answer(mix, cfg_text, ctx["bench_dir"])
    ref_s = time.perf_counter() - t_ref
    if not rehearsal:
        check_pins(ref, pins)
        for key in ("fingerprinted", "discarded"):
            need(ref[key] == pins[key],
                 f"plain reference {key} {ref[key]} != pin {pins[key]}")
    say(f"bench: plain reference {ref['generated']} generated / "
        f"{ref['distinct']} distinct / diameter {ref['diameter']} / "
        f"{ref['fingerprinted']} fingerprinted, {ref['discarded']} of them "
        f"discarded in {ref_s:.2f}s; the program compiled {compiled}")
    results = [("warm-up", warm)] + [(f"search[{i}]", got)
                                     for i, (_, got) in enumerate(searches)]
    # both comparisons of every search are made and printed
    good = [compare(got, ref, label) & compare_discarded(got, ref, label)
            for label, got in results]
    failed = good[1:].count(False)
    rate = sum(g["generated"] for _, g in searches) / window_wall
    say(f"bench: {len(searches)} search(es) in {window_wall:.3f}s"
        f"{' (traced)' if traced else ''}; search walls "
        f"{[round(dt, 3) for dt, _ in searches]}")
    return {
        "attempted": len(searches), "failed": failed,
        "correct": all(good),
        "values": {"states_per_s": rate, "setup_s": setup_s},
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs),
                   "memory_peak_bytes": int(peak)},
        "trace_dir": trace_dir if traced else None,
        "artifacts": {"at_window": at_window, "after": after,
                      "searches": len(searches), "reference": ref,
                      "seen_mode": warm["seen_mode"],
                      "constraints_compiled": compiled},
    }
