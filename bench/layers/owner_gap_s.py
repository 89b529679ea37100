"""The serial owner's seconds BETWEEN two jobs: the distinct owner envelopes
of the run sorted by `owner_began_at`, mean of `began[n+1] - ended[n]` over
the window's envelopes (bench/stations.py `owner_gaps`) — the summary, the
answer's way through the pipe, the daemon's lock changing hands, the next
request's way in; they divide `states_per_s` exactly as a job's own seconds
do, and no span of a job holds them.  None where the artifacts carry no
stations (before PR 49) or the window holds fewer than two envelopes."""

import served
import stations


def read(run):
    return served.mean_of(stations.owner_gaps(run))
