"""Seconds of a cohort's run: the span `batch.run` round
`BatchCheckEngine.run()` in the leader's artifact — the init states, one
thread a member through its host_seen loop, every superstep, each member's
finalized checkpoint — up to the last member's end; per window commit.  None
where the program has no such span (before PR 39)."""

import cohorts


def read(run):
    return cohorts.per_commit(run, cohorts.phase("batch.run"))
