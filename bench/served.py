"""What the per-layer readers of a served cell share: the window's jobs as
the `stream` driver summarises them (`artifacts["jobs"]`: the client's
clock, the record's, and the phases, counters and serve block of the
artifact each client read back), and the owner's trace cut by the program's
spans.  Every function returns None where there is nothing to read — another
driver's run, an untraced one — and never raises for that."""

from __future__ import annotations

import statistics

#: program spans of a job in the owner that are neither build nor envelope
NOT_BUILD = ("jaxmc.search", "jaxmc.checkpoint", "jaxmc.level",
             "jaxmc.tier", "jaxmc.device_init")


def jobs(run, kind=None, label=None):
    """The window's finished jobs; `kind` "edit" (searched) or "rerun"
    (replayed), `label` the suite's ("3p", "4p8")."""
    art = (run.get("out") or {}).get("artifacts") or {}
    out = art.get("jobs")
    if not isinstance(out, list):
        return []
    return [j for j in out if j.get("status") == "done"
            and (kind is None or j.get("kind") == kind)
            and (label is None or j.get("label") == label)]


def mean_of(values):
    values = [v for v in values if v is not None]
    return statistics.fmean(values) if values else None


def per_searched_job(run, pick):
    """Mean over the searched jobs of `pick(job)`; a job where it is None
    is left out."""
    return mean_of(pick(j) for j in jobs(run, "edit"))


def phase_s(names):
    return lambda j: (sum(j["phases"].get(n, 0.0) for n in names)
                      if any(n in j["phases"] for n in names) else None)


def _owner_trace(run, search):
    """bench/spans.py over the owner's trace with `search` as the span to
    cut; None without a trace or where it holds no name of the program's."""
    trace_dir = (run.get("out") or {}).get("trace_dir")
    if not trace_dir:
        return None
    import reduce
    import spans
    path = reduce.newest_xplane(trace_dir)
    an = spans.analyze(path, "bench.window", search) if path else None
    return an if an and an["named"] and an["searches"] else None


def owner_idle_s(run, pick):
    """Device-idle seconds of the traced window under the `jaxmc.*` host
    spans `pick(name)` accepts, per SEARCHED job: the whole owner window is
    the span to cut, so the time between two jobs counts too."""
    an, n = _owner_trace(run, "bench.window"), len(jobs(run, "edit"))
    if not an or not n:
        return None
    return sum(v for k, v in an["idle_s"].items() if pick(k)) / n


def owner_searches(run):
    """The owner's trace cut by `jaxmc.search` (session.py's span round
    `explore()`, one a job): busy and idle seconds inside the searches."""
    return _owner_trace(run, "jaxmc.search")


if __name__ == "__main__":
    # python3 bench/served.py <trace dir>: the owner window's device-idle
    # seconds by innermost program span, and its busy seconds
    import json
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import reduce
    import spans
    an = spans.analyze(reduce.newest_xplane(sys.argv[1]), "bench.window",
                       "bench.window")
    print(json.dumps({"busy_s": an["search_busy_s"],
                      "idle_s": dict(sorted(an["idle_s"].items(),
                                            key=lambda kv: -kv[1]))},
                     indent=1))
