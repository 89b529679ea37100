"""Seconds under `checkpoint.write` in a commit: every member's finalized
checkpoint (its native store dumped, pickled, hashed and written with an
fsync), summed over the members, per window commit."""

import cohorts


def read(run):
    return cohorts.per_commit(run, cohorts.phase("checkpoint.write"))
