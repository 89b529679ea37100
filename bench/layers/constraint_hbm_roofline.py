"""Per cent of the HBM roofline of the CONSTRAINT branch: the bytes that
judging and keeping MUST move (bench/shapes_constraint.py: every row that
entered the seen table in the traced searches read once, every kept row read
and written once, at the mix's packed `state_words` — `search.rows_new` and
`search.rows_discarded`, the rows and never the capacities) over the chip's
peak bandwidth (bench/peaks.json, by device_kind), against its device
seconds (scope `jaxmc.constraint`, bench/spans.py).  The predicate is
compare-and-mask work on what was read: no floating-point operation to
count.  None without a trace, where the program has no such counters, or
where no operation carries the scope — never a guess."""

import os

import spans
from lib import load_json, load_module


def read(run):
    an = spans.of_run(run)
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        kept, gone = (b[k] - a.get(k, 0) for k in (
            "search.rows_new", "search.rows_discarded"))
        words = run["mix"]["state_words"]
    except (KeyError, TypeError):
        return None
    seconds = an and an["scope_s"].get("jaxmc.constraint")
    if not seconds or not kept + gone:
        return None
    bench = run["bench_dir"]
    nbytes = load_module(
        os.path.join(bench, "shapes_constraint.py"),
        "bench_shapes_constraint").constraint_bytes(kept + gone, kept, words)
    shapes = load_module(os.path.join(bench, "shapes.py"), "bench_shapes")
    peak = shapes.peak_for(run["out"]["device"]["kind"],
                           load_json(os.path.join(bench, "peaks.json")))
    return shapes.roofline_share(nbytes, seconds, peak["hbm_bytes_per_s"])
