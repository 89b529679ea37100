#!/usr/bin/env python3
"""One cell, once:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures, prints the contract's one JSON object as the
LAST line of stdout and exits 0.  It prints NO result line and exits
non-zero when jax finds no TPU (or fewer chips than the cell asks for), when
a run demotes to the CPU, when a search finishes anywhere but on the device,
or in a directory that holds no jaxmc checkout.

This file only dispatches by name (README.md): the cell's configuration,
mix, pins, driver and per-layer readers are files of their own, found
through BENCHMARK.json.  It never imports jax: the process that runs the
search must be the one that takes the chip.

`--rehearse-on-cpu` runs the same plumbing at toy size on XLA:CPU, says so,
and prints no result object: it proves nothing about the chip.
"""

import time

T0 = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import lib  # noqa: E402


def per_layer(res: dict, out: dict, trace) -> dict:
    """Each per-layer metric from a reader of its own; a reader that finds
    nothing to read returns None and the metric is left out."""
    run = {"out": out, "trace": trace, "mix": res["mix"],
           "pins": res["pins"], "cell": res["cell"],
           "bench_dir": res["bench_dir"]}
    metrics = {}
    for m in res["per_layer"]:
        reader = lib.load_module(res["reader_path"](m["name"]),
                                 "bench_layer_" + m["name"])
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    args = ap.parse_args(argv)
    try:
        lib.need(os.path.isdir(os.path.join(lib.ROOT, "jaxmc")),
                 "the jaxmc checkout is not here: nothing to measure")
        res = lib.resolve(args.workload)
        seconds = args.seconds if args.seconds is not None \
            else res["benchmark"]["run_seconds"]
        driver = lib.load_module(res["driver_path"],
                                 "bench_driver_" + res["mix"]["driver"])
        if args.rehearse_on_cpu:
            lib.say("bench: REHEARSAL on XLA:CPU at toy size: NOT a chip "
                    "run, no result")
        ctx = dict(res, seed=args.seed, seconds=seconds,
                   trace=bool(args.trace), rehearsal=args.rehearse_on_cpu,
                   t0=T0)
        out = driver.run(ctx)
        trace = None
        if out.get("trace_dir"):
            import reduce as reduction
            path = reduction.newest_xplane(out["trace_dir"])
            lib.need(path, f"tracing left no .xplane.pb in "
                           f"{out['trace_dir']}")
            trace = reduction.reduce_trace(path)
            lib.need(trace, "the trace holds no bench.window span")
        if args.trace:
            metrics = per_layer(res, out, trace)
        else:
            metrics = {m["name"]: {"value": float(out["values"][m["name"]]),
                                   "unit": m["unit"]}
                       for m in res["end_to_end"]}
        device = dict(out["device"])
        breakdown = None
        if args.trace and trace:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            breakdown = {"device_ops": trace["device_ops"],
                         "idle_gaps": trace["idle_gaps"]}
        for name, m in metrics.items():
            lib.say(f"bench: {name} = {m['value']!r} {m['unit']}")
        if args.rehearse_on_cpu:
            lib.say(f"bench: rehearsal ended (correct={out['correct']}, "
                    f"attempted={out['attempted']}, "
                    f"failed={out['failed']}): NOT a chip run, no result")
            return 0 if out["correct"] else 1
        lib.need(device["platform"] == "tpu",
                 f"ran on {device['platform']!r}, not on a TPU")
        if args.trace:
            lib.need(device.get("busy_s", 0) > 0,
                     "the traced window shows no operation on the device")
        print(lib.result_line(out["correct"], out["attempted"],
                              out["failed"], metrics, device, breakdown),
              flush=True)
        return 0
    except lib.BenchFailure as ex:
        print(f"bench: FAILED, no result: {ex}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
