"""Seconds under `checkpoint.write` in a searched job: the seen table pulled
to the host, pickled, hashed and written with an fsync after EVERY job (the
serve path's finalized checkpoint); mean over the window's searched jobs."""

import served


def read(run):
    return served.per_searched_job(run,
                                   served.phase_s(("checkpoint.write",)))
