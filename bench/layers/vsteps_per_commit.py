"""Vmapped dispatches a commit makes: `batch_dispatches` of the `serve`
block, once for each distinct vbatch that answered the window's jobs (a job
that ran alone counts its own `bfs.hstep` dispatches), over the window's
commits."""

import cohorts


def read(run):
    return cohorts.vsteps_per_commit(run)
