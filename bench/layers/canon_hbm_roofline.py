"""Per cent of the HBM roofline of the SYMMETRY canonicaliser: the bytes it
must move (bench/shapes_symmetry.py: every row it took read and written once
at its unpacked lane width — `search.canon_rows` of the traced searches less
the initial states, which the host's side took at the build, x the mix's
`row_lanes`) over the chip's peak bandwidth (bench/peaks.json, by
device_kind), against its device seconds (scope `jaxmc.canon`,
bench/spans.py).  The network is compare-and-select work on what was read:
no floating-point operation to count.  None without a trace, where the
program has no such counter, or where no operation carries the scope (XLA
fused the network away: bench/SPANS.symmetry.md) — never a guess."""

import os

import spans
from lib import load_json, load_module


def read(run):
    an = spans.of_run(run)
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        rows = b["search.canon_rows"] - a.get("search.canon_rows", 0)
        lanes = run["mix"]["row_lanes"]
        # the initial states went through the function on the host's
        # side, at the build: they are no work of the window's device
        pins = run["pins"]
        rows -= art["searches"] * (pins["generated"] - sum(
            cand for _, cand, _ in pins["levels"]))
    except (KeyError, TypeError):
        return None
    canon_s = an and an["scope_s"].get("jaxmc.canon")
    if not canon_s or not rows:
        return None
    bench = run["bench_dir"]
    nbytes = load_module(os.path.join(bench, "shapes_symmetry.py"),
                         "bench_shapes_symmetry").canon_bytes(rows, lanes)
    shapes = load_module(os.path.join(bench, "shapes.py"), "bench_shapes")
    peak = shapes.peak_for(run["out"]["device"]["kind"],
                           load_json(os.path.join(bench, "peaks.json")))
    return shapes.roofline_share(nbytes, canon_s, peak["hbm_bytes_per_s"])
