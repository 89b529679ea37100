"""What the readers of a served job's STATIONS share (PR 49;
`serve/protocol.py` "A job's clock", bench/SPANS.owner.md): the wall-clock
marks the daemon and the owner stamp into every served job's artifact,
`serve.stations`, which the `stream` and `cohort` drivers hand the readers
with the whole `serve` block (`artifacts["jobs"]`, `artifacts["warmup"]`).
All on ONE host's `time.time()`: the daemon's and its owner child's.  Every
function returns None where there is nothing to read — a program from before
the stations (the parent of PR 49), another driver's run, a job no owner
ran — and never raises for that."""

from __future__ import annotations

import served


def of(j):
    """A job's stations, or {}."""
    st = (j.get("serve") or {}).get("stations")
    return st if isinstance(st, dict) else {}


def between(a: str, b: str, less=None):
    """`job -> stations[b] - stations[a]` (less `less(job)`, where given);
    None where the job passed either station not."""
    def pick(j):
        st = of(j)
        if st.get(a) is None or st.get(b) is None:
            return None
        d = st[b] - st[a]
        if less is not None:
            sub = less(j)
            if sub is None:
                return None
            d -= sub
        return d
    return pick


def per_job(run, a: str, b: str):
    """Mean of `stations[b] - stations[a]` over ALL the window's jobs (as
    `queue_wait_s` averages)."""
    return served.mean_of(map(between(a, b), served.jobs(run)))


def envelopes(jobs):
    """The DISTINCT owner requests that answered `jobs`, as (began, ended,
    job) sorted by `owner_began_at`: the members of one vbatch and the
    followers of one signature carry their leader's owner stations, so they
    are one envelope."""
    seen = {}
    for j in jobs:
        st = of(j)
        if st.get("owner_began_at") is None or \
                st.get("owner_ended_at") is None:
            continue
        seen.setdefault((st["owner_began_at"], st["owner_ended_at"]), j)
    return [(b, e, j) for (b, e), j in sorted(seen.items())]


def per_envelope(run, pick, kind="edit"):
    """Mean of `pick(job)` over the distinct owner requests that answered
    the window's jobs of `kind` (None: all): once a vbatch, not once a
    member."""
    return served.mean_of(pick(j) for _, _, j in
                          envelopes(served.jobs(run, kind)))


def owner_gaps(run):
    """Seconds between two consecutive jobs of the serial owner,
    `began[n+1] - ended[n]`, for every envelope of the WINDOW that has a
    known predecessor: the set-up's jobs (`artifacts["warmup"]`: the
    primers, the runners' warm-up commits, which may run inside the window)
    are envelopes too, so that a gap never spans a job the window does not
    list."""
    art = (run.get("out") or {}).get("artifacts") or {}
    window = served.jobs(run)
    warm = [j for j in art.get("warmup") or []
            if isinstance(j, dict) and j.get("status") == "done"]
    mine = {(b, e) for b, e, _ in envelopes(window)}
    env = envelopes(warm + window)
    return [b1 - e0 for (_, e0, _), (b1, e1, _) in zip(env, env[1:])
            if (b1, e1) in mine]
