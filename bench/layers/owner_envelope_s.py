"""What an owner request costs beside the run: `owner_received_at -
owner_sent_at - job_wall_s` (the daemon's two marks round the pipe, less the
owner's own wall from `run_solo` / `run_vbatch`'s `t0`): the pipe both ways,
the request's unpickling, `jt.summary()`, `close()`, the answer's pickling;
mean over the DISTINCT owner requests that answered the window's searched
jobs — a cohort's once per vbatch, not once a member.  None where the
artifacts carry no stations (before PR 49)."""

import stations


def read(run):
    return stations.per_envelope(run, stations.between(
        "owner_sent_at", "owner_received_at",
        less=lambda j: j["serve"].get("job_wall_s")))
