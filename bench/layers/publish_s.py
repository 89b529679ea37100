"""Seconds from the owner's answer to the job's final record: `finished_at -
owner_received_at` of `serve.stations` — the daemon's counters, the
artifact's write (`save_result`; a cohort's members are published one after
the other, so a later member waits for the earlier ones') up to the stamp
the record's last write carries; mean over ALL the window's jobs.  None
where the artifacts carry no stations (before PR 49) or no owner ran a
job."""

import stations


def read(run):
    return stations.per_job(run, "owner_received_at", "finished_at")
