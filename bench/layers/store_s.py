"""Seconds a commit's members spend in the native fingerprint store: the
float counter `hostseen.store_s` of every member's artifact (`insert` of a
chunk's valid keys, the key columns' copy included; `contains` under POR),
summed over the members, per window commit.  None where the program has no
such counter (before PR 39)."""

import cohorts


def read(run):
    return cohorts.per_commit(run, cohorts.counter("hostseen.store_s"))
