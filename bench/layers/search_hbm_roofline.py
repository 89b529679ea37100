"""Per cent of the HBM roofline of one whole search: the bytes it must move
(bench/shapes.py, a floor from shapes) over the chip's peak bandwidth
(bench/peaks.json, by device_kind), against the device-busy seconds of the
traced searches (bench/reduce.py).  Memory-bound by construction: the
search does no floating-point work."""

import os

from lib import load_json, load_module


def read(run):
    tr = run["trace"]
    if not tr or not tr["searches_traced"] or tr["search_busy_s"] <= 0:
        return None
    shapes = load_module(os.path.join(run["bench_dir"], "shapes.py"),
                         "bench_shapes")
    peaks = load_json(os.path.join(run["bench_dir"], "peaks.json"))
    peak = shapes.peak_for(run["out"]["device"]["kind"], peaks)
    ref, mix = run["out"]["artifacts"]["reference"], run["mix"]
    nbytes = shapes.search_bytes(ref["generated"], ref["distinct"],
                                 mix["state_words"], mix["key_words"])
    return shapes.roofline_share(
        nbytes, tr["search_busy_s"] / tr["searches_traced"],
        peak["hbm_bytes_per_s"])
