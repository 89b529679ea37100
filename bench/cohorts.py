"""What the per-layer readers of the cohort cell share: the window's jobs as
the `cohort` driver summarises them (the `stream` driver's shape,
`artifacts["jobs"]`), grouped into the vbatches that answered them, and the
owner's trace read by the span round a vmapped dispatch.  A commit's numbers
are SUMS over its jobs divided by the window's commits: the cohort's own
spans and counters (`batch.*`) are in its leader's artifact alone, the
members' (`batch.barrier_wait_s`, `hostseen.*`, `checkpoint.write`) in each
member's.  Every function returns None where there is nothing to read — a
program from before the spans, another driver's run, an untraced one — and
never raises for that."""

from __future__ import annotations

import functools

import served

DISPATCH_SPAN = "jaxmc.batch.dispatch"


def fire_s(j):
    """A job's host seconds firing supersteps: the span `batch.dispatch`
    and the float counters `batch.stack_s` / `batch.unstack_s` round it
    (the leader's artifact has them); None where it has no such span."""
    if "batch.dispatch" not in j["phases"]:
        return None
    return j["phases"]["batch.dispatch"] + \
        j["counters"].get("batch.stack_s", 0.0) + \
        j["counters"].get("batch.unstack_s", 0.0)


def jobs(run):
    return served.jobs(run, "edit")


def commits(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    return art.get("commits") or None


def per_commit(run, pick):
    """Sum of `pick(job)` over the window's jobs over its commits; None
    where no job has the number."""
    values = [v for v in (pick(j) for j in jobs(run)) if v is not None]
    n = commits(run)
    return sum(values) / n if values and n else None


def phase(*names):
    return served.phase_s(names)


def counter(name):
    return lambda j: j["counters"].get(name)


def vbatches(run):
    """The distinct runs that answered the window's jobs, a job alone
    included: [{"occupancy", "dispatches"}] — `batch_occupancy` and
    `batch_dispatches` of the `serve` block, which every member of one
    vbatch carries alike; a solo job is a run of width 1 whose dispatches
    are its own `bfs.hstep` site's."""
    seen = {}
    for j in jobs(run):
        sv = j["serve"]
        ids = tuple(sorted([j["id"]] + list(sv.get("batched_with") or [])))
        if ids in seen:
            continue
        solo = sv.get("batch_occupancy") is None
        seen[ids] = {
            "occupancy": 1 if solo else sv["batch_occupancy"],
            "dispatches": j["dispatches"].get("bfs.hstep", 0) if solo
            else sv.get("batch_dispatches") or 0}
    return list(seen.values())


def vsteps_per_commit(run):
    n = commits(run)
    vb = vbatches(run)
    return sum(b["dispatches"] for b in vb) / n if vb and n else None


@functools.lru_cache(maxsize=4)   # three trace readers, one parse
def spans_in_window(path: str, window: str = "bench.window",
                    span: str = DISPATCH_SPAN):
    """How many host spans named `span` the trace at `path` holds inside
    its window span (clipped to it); None if it holds no window."""
    import reduce as R   # bench/ is on sys.path wherever this runs
    import xmeta as X
    wins, cuts = [], []
    for plane in X.read(path):
        if plane["name"] != R.HOST_PLANE:
            continue
        meta = plane["event_metadata"]
        for line in plane["lines"]:
            for mid, s, d in line["events"]:
                name = meta[mid]["name"]
                if name == window:
                    wins.append((s, s + d))
                elif name == span:
                    cuts.append((s, s + d))
    if not wins:
        return None
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    return len(R.clip(cuts, lo, hi))


def dispatches_traced(run):
    """The `jaxmc.batch.dispatch` spans inside the traced window; None
    without a trace, or where it holds none (a program from before
    PR 39)."""
    trace_dir = (run.get("out") or {}).get("trace_dir")
    if not trace_dir:
        return None
    import reduce
    path = reduce.newest_xplane(trace_dir)
    return (spans_in_window(path) or None) if path else None


def dispatch_device_s(run):
    """Device-busy seconds a vmapped dispatch: the traced window's busy
    seconds (`run["trace"]["busy_s"]`, bench/reduce.py) over the
    `jaxmc.batch.dispatch` spans inside it.  Every device operation of a
    window is the vmapped program's but the init states' keys (one tiny
    `bfs.host_keys` dispatch a member).  NOT the busy seconds UNDER the
    spans: the trace's device clock runs milliseconds off the host's, and
    two thirds of the operations land outside their own dispatch's 7 ms
    span (PR 39, 620,096 operations of 7,288 dispatches looked at one by
    one: 0.0845 s of the window's 0.2499 lie under a span) — a span's
    count is sound where its borders are not.  None without a trace or
    where it holds no such span (a program from before PR 39)."""
    n, tr = dispatches_traced(run), run.get("trace")
    if not n or not tr or tr.get("busy_s") is None:
        return None
    return tr["busy_s"] / n


def vstep_device_s(run):
    """`dispatch_device_s` times the dispatches a commit makes (the traced
    window holds the other runners' warm-up cohorts too, so its busy
    seconds are not divided by the commits)."""
    dev, per = dispatch_device_s(run), vsteps_per_commit(run)
    return None if dev is None or not per else dev * per
