"""Seconds a commit spends BUILDING its cohort before the first dispatch: the
span `batch.build` round `BatchCheckEngine.build()` in the leader's artifact
— every member loaded and bounds-analysed, the others sampled with the
interpreter (`batch_sample`), the one donor engine (`engine_build`: its own
sample, the lane plan over the union, the kernels), the follower clones;
per window commit.  None where the program has no such span (before
PR 39 the cohort's build spans reached no artifact)."""

import cohorts


def read(run):
    return cohorts.per_commit(run, cohorts.phase("batch.build"))
