"""Per cent of the HBM roofline of the backward walk: the bytes it must move
(bench/shapes_trace.py: the log rows it really expanded, once each, plus the
result block — `search.trace_rows_expanded` and `search.trace_len` of the
traced searches) over the chip's peak bandwidth (bench/peaks.json, by
device_kind), against the walk's device seconds (scope `jaxmc.trace.walk`,
bench/spans.py).  Memory-bound by construction: the walk does no
floating-point work.  None without a trace or where the program has no such
scope or counters (before PR 44)."""

import os

import spans
from lib import load_json, load_module


def read(run):
    an = spans.of_run(run)
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        rows, states = (b[k] - a.get(k, 0) for k in
                        ("search.trace_rows_expanded", "search.trace_len"))
    except (KeyError, TypeError):
        return None
    walk_s = an and an["scope_s"].get("jaxmc.trace.walk")
    if not walk_s:
        return None
    bench = run["bench_dir"]
    nbytes = load_module(os.path.join(bench, "shapes_trace.py"),
                         "bench_shapes_trace").walk_bytes(
        rows, states, run["mix"]["state_words"])
    shapes = load_module(os.path.join(bench, "shapes.py"), "bench_shapes")
    peak = shapes.peak_for(run["out"]["device"]["kind"],
                           load_json(os.path.join(bench, "peaks.json")))
    return shapes.roofline_share(nbytes, walk_s, peak["hbm_bytes_per_s"])
