"""Median client round trip of byte-identical resubmissions to the warm
daemon, one at a time, after the verdict.  A warm repeat is answered from
the finalized checkpoint with no search: a HOST-ONLY number of some tens of
milliseconds that swings by a tenth on a shared host, which is why it is a
per-layer reading and judges nothing (PR 22)."""

import statistics


def read(run):
    times = run["out"]["artifacts"].get("rerun_s")
    return statistics.median(times) if times else None
