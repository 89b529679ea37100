"""Driver for the `counterexample` kind of traffic: whole searches of a cfg
whose invariant FAILS, on one built engine in THIS process (which therefore
holds the chip), one after another, until the window has passed.  The
`recheck` driver's shape (its counters and result fields are its own
functions, loaded from its file), with what `recheck` and `lib.compare` never
look at: the behaviour each search returns.

Set-up (counted in `setup_s`): write the seed's cfg, jax and chip init,
parse, kernel build, one warm-up search (it compiles, or loads from the
checkout's cache, every program the window drives: the search's and the
trace walk's).  Window: `explore()` again and again on the same engine; the
reconstruction of the counterexample is INSIDE each timed search.
`states_per_s` = the `generated` of the window's searches / the window's wall.

`correct`: every search's counts equal the plain reference's at its
whole-level stop (`lib.compare`, limit 0), its verdict names the reference's
invariant, and ITS OWN trace passes the reference's `check_trace`.

An engine that keeps no trace under these options (the program before PR 44)
ends the run at once, with no result: after the build where the engine says
so itself, else after the warm-up search.
"""

from __future__ import annotations

import os
import sys
import time

from lib import (BenchFailure, check_pins, compare, load_module, need, say,
                 work_dir, write_seed_cfg)


def plain_state(state) -> dict:
    """A decoded state in plain Python for the reference, which knows
    nothing of jaxmc's values: functions become dicts over the names of
    their domain, everything else stays (ints, strings)."""
    return {var: ({str(k): v for k, v in val.d.items()}
                  if hasattr(val, "d") else val)
            for var, val in state.items()}


def verdict_of(res) -> dict:
    """What a search says beside its counts: the violation's kind and name
    and the behaviour, as (plain state, label) lists."""
    v = res.violation
    if v is None:
        return {"kind": None, "name": None, "states": [], "labels": []}
    return {"kind": v.kind, "name": v.name,
            "states": [plain_state(st) for st, _ in v.trace],
            "labels": [label for _, label in v.trace]}


def judge(got: dict, ref: dict, ref_mod, n: int, m: int, label: str) -> bool:
    """One search against the reference: the counts (`lib.compare`), the
    verdict's name, and the trace by `check_trace`.  Prints each."""
    ok = compare(got, ref, label)
    want = ("invariant", ref["invariant"])
    good = (got["kind"], got["name"]) == want
    say(f"  compare {label} verdict: program {(got['kind'], got['name'])} "
        f"reference {want} {'ok' if good else 'FAILED'}")
    ok = ok and good
    good, why = ref_mod.check_trace(got["states"], got["labels"], n, m,
                                    ref["invariant"],
                                    min_len=ref["diameter"] + 1)
    say(f"  compare {label} trace: {len(got['states'])} states, shortest "
        f"{ref['diameter'] + 1}: {why} {'ok' if good else 'FAILED'}")
    return ok and good


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
    tel = obs.Telemetry(meta={"command": "bench.counterexample",
                              "workload": ctx["cell"]["name"]})

    def search():
        res = sess.explore()
        return dict(recheck._result_dict(res, sess), **verdict_of(res))

    with obs.use(tel):
        sess = CheckSession(SessionConfig(
            spec=os.path.join(root, mix["spec"]), cfg=cfg_path, **opts),
            tel=tel)
        try:
            sess.compile()
        except Exception as ex:  # noqa: BLE001 — no chip, no result
            raise BenchFailure(f"engine did not come up on {platform!r}: "
                               f"{type(ex).__name__}: {ex}") from ex
        need(getattr(sess.engine, "store_trace", True),
             "the engine keeps no counterexample trace under these options "
             f"({mix['session']}): nothing to judge")
        devs = jax.devices()
        need(devs[0].platform == platform,
             f"jax initialized {devs[0].platform!r}, not {platform!r}")
        need(len(devs) >= ctx["cell"]["chips"],
             f"{len(devs)} device(s), the cell asks for "
             f"{ctx['cell']['chips']}")
        # ---- warm-up: every program of the window, on the same engine
        with tel.span("bench.warmup"):
            warm = search()
        need(len(warm["states"]) > 1,
             f"the warm-up search returned no trace ({warm['labels']}): "
             f"nothing to judge")
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
    ref_mod = load_module(os.path.join(ctx["bench_dir"], "reference",
                                       mix["reference"] + ".py"),
                          "bench_reference_" + mix["reference"])
    t_ref = time.perf_counter()
    n, m, invariants = ref_mod.parse_cfg(cfg_text)
    ref = ref_mod.explore(n, m, invariants)
    ref_s = time.perf_counter() - t_ref
    need(not ref["ok"], "the plain reference finds no violation: the cfg "
                        "is not this kind of traffic")
    if not rehearsal:
        check_pins(ref, pins)
        need((ref["invariant"], ref["diameter"] + 1) ==
             (pins["invariant"], pins["trace_len"]),
             f"plain reference {ref['invariant']} at depth "
             f"{ref['diameter']} != pins")
    say(f"bench: plain reference {ref['generated']} generated / "
        f"{ref['distinct']} distinct, {ref['invariant']} first false at "
        f"depth {ref['diameter']} in {ref_s:.2f}s")
    warm_ok = judge(warm, ref, ref_mod, n, m, "warm-up")
    failed = sum(0 if judge(got, ref, ref_mod, n, m, f"search[{i}]") else 1
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
                      "behaviour": list(zip(warm["labels"],
                                            warm["states"]))},
    }
