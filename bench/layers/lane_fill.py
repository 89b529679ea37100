"""Per cent of the member lanes of a commit's dispatches that held a chunk:
the counter `batch.lane_steps` (the sum of the dispatches' widths) over
members x `batch.dispatches`, both of the leader's artifact, over the
window's cohorts.  A member that finishes leaves its lane idle-masked for
the rest of the cohort, so a ragged matrix reads well under 100.  None where
the program has no such counter (before PR 39)."""

import cohorts


def read(run):
    lanes = slots = 0
    for j in cohorts.jobs(run):
        c = j["counters"]
        if "batch.lane_steps" in c and c.get("batch.dispatches"):
            lanes += c["batch.lane_steps"]
            slots += c["batch.dispatches"] * \
                (1 + len(j["serve"].get("batched_with") or []))
    return 100.0 * lanes / slots if slots else None
