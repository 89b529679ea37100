"""Median client wall, POST sent to result read back, of the window's
SEARCHED 4p8 jobs: the served latency of the suite's long job with two
runners keeping the queue full.  Per-layer: it judges nothing."""

import statistics

import served


def read(run):
    walls = [j["client_s"] for j in served.jobs(run, "edit", "4p8")]
    return statistics.median(walls) if walls else None
