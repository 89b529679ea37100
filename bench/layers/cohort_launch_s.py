"""Host seconds of a cohort's FIRST call of its vmapped program, up to the
call's return: the float counter `batch.first_dispatch_s` in the leader's
artifact — `jax.jit(jax.vmap(core))` is made anew for every cohort, so the
first call traces, lowers and compiles or loads the program (and enqueues
one dispatch); per window commit.  None where the program has no such
counter (before PR 39)."""

import cohorts


def read(run):
    return cohorts.per_commit(run, cohorts.counter("batch.first_dispatch_s"))
