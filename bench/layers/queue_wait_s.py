"""Seconds between a job's `submitted_at` and `started_at` in its record (the
daemon's clock, `serve/protocol.py`): from the record made in `submit()` to a
worker marking the job running — the spool's hard write and the wait in the
daemon's queue for a free worker; mean over ALL the window's jobs.  None
where a record lacks either."""

import served


def read(run):
    def wait(j):
        if j.get("submitted_at") is None or j.get("started_at") is None:
            return None
        return j["started_at"] - j["submitted_at"]
    return served.mean_of(wait(j) for j in served.jobs(run))
