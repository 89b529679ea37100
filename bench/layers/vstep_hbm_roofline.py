"""Per cent of the HBM roofline of the cohort's vmapped program: the bytes of
its arguments and results a dispatch (bench/shapes_cohort.py, a floor from
the mix's `vstep` shapes, padding counted) over the chip's peak bandwidth
(bench/peaks.json, by device_kind), against the device-busy seconds a
dispatch in the owner's trace (bench/cohorts.py `dispatch_device_s`: the
window's busy seconds over its `jaxmc.batch.dispatch` spans).  Memory-bound
by construction: the step does no floating-point work.  None without a trace
or where the program has no such span (before PR 39)."""

import os

import cohorts
from lib import load_json, load_module


def read(run):
    dev = cohorts.dispatch_device_s(run)
    if not dev:
        return None
    bench = run["bench_dir"]
    nbytes = load_module(os.path.join(bench, "shapes_cohort.py"),
                         "bench_shapes_cohort").vstep_bytes_of(run["mix"])
    if nbytes is None:
        return None
    shapes = load_module(os.path.join(bench, "shapes.py"), "bench_shapes")
    peak = shapes.peak_for(run["out"]["device"]["kind"],
                           load_json(os.path.join(bench, "peaks.json")))
    return shapes.roofline_share(nbytes, dev, peak["hbm_bytes_per_s"])
