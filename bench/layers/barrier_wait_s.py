"""Seconds a commit's members spend in a superstep NOT firing it: the float
counter `batch.barrier_wait_s` of every member's artifact, summed over the
members — the wait for the dispatcher's lock, for the slower members' chunks
and for the dispatch another member's thread runs (up to four threads wait
at once, so the sum can pass the cohort's wall); per window commit.  None
where the program has no such counter (before PR 39)."""

import cohorts


def read(run):
    return cohorts.per_commit(run, cohorts.counter("batch.barrier_wait_s"))
