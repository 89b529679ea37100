"""Seconds a CLAIMED job waits for the device owner: `owner_sent_at -
claimed_at` of `serve.stations` (the daemon's clock; `claimed_at` is the
record's `started_at`, where `queue_wait_s` ends) — a worker marked the job
running, then stood on `DeviceOwner._lock` behind the other worker's whole
job or cohort (and a spawn, where the owner had to be) until its request
left the pipe; mean over ALL the window's jobs, as `queue_wait_s`.  None
where the artifacts carry no stations (before PR 49) or no owner ran a
job."""

import stations


def read(run):
    return stations.per_job(run, "claimed_at", "owner_sent_at")
