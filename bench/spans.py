"""The program's own names in a trace: device seconds per kernel scope and
device-idle seconds per program span, inside the `bench.search` spans.

The kernels of `jaxmc/backend/bfs.py` run under `jax.named_scope`s
(`jaxmc.expand`, `jaxmc.keys`, `jaxmc.merge.sort`, `jaxmc.merge.probe`,
`jaxmc.merge.scatter`, `jaxmc.compact`, `jaxmc.scan`); XLA keeps the scope
path in each operation's `op_name`, which the trace carries as the stat
`tf_op` of the event's metadata (read by `xmeta.py`; a fusion carries the
`op_name` of its root).  `jaxmc.obs.Telemetry.span()` writes every program
span as a `jaxmc.<span>` TraceAnnotation on the host's lines of the same
trace.  From one `.xplane.pb`, parsed once per path:

  scope_s   device SELF seconds (reduce.self_times: a `while` does not
            count its body twice) per innermost `jaxmc.*` component of the
            operation's `tf_op`, else "unscoped"; they add up to the search
            busy time of `reduce.reduce_trace`
  idle_s    device-idle seconds per innermost `jaxmc.*` host span over each
            piece of each gap (a gap is cut where spans begin and end), else
            "unattributed"; they add up to the idle time inside the
            searches.  The envelope `jaxmc.search` (session.py's span round
            the whole of explore()) names no code, so time under it alone
            is unattributed.

A compile cache filled by a commit WITHOUT the scopes serves executables
that carry that commit's `op_name`s (jax's cache key strips debug info):
the scopes then vanish from the trace and everything reads "unscoped".
`unscoped_device_share` then says 100 and `device_s` gives None, never a
zero: seconds per kernel that were not read must not become a baseline.
"""

from __future__ import annotations

import functools
import os
import sys

PREFIX = "jaxmc."
ENVELOPE = ("jaxmc.search",)
UNSCOPED, UNATTRIBUTED = "unscoped", "unattributed"


def scope_of(tf_op) -> str:
    """The innermost `jaxmc.` component of an HLO op_name."""
    for part in reversed((tf_op or "").rstrip(":").split("/")):
        if part.startswith(PREFIX):
            return part
    return UNSCOPED


def span_idle(gaps, spans):
    """{span name | "unattributed": ns} over the gap intervals: each gap is
    cut at every span boundary inside it and each piece goes to the SHORTEST
    span that covers it."""
    out = {}
    for a, b in gaps:
        cuts = sorted({a, b} | {t for _, s, e in spans for t in (s, e)
                                if a < t < b})
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            cover = [(e - s, n) for n, s, e in spans if s <= mid < e]
            name = min(cover)[1] if cover else UNATTRIBUTED
            out[name] = out.get(name, 0.0) + (hi - lo)
    return out


@functools.lru_cache(maxsize=8)   # eleven trace readers, one parse
def analyze(path: str, window: str = "bench.window",
            search: str = "bench.search"):
    """The trace at `path` reduced to {"searches", "scope_s", "idle_s",
    "search_busy_s", "search_idle_s", "named", "scoped"}; seconds are totals
    over the
    traced searches, averaged over the chips that ran anything.  None if the
    trace holds no window span."""
    import reduce as R   # bench/ is on sys.path wherever this runs:
    import xmeta as X    # run.py, bench/tests/conftest.py, __main__
    host, devices = [], {}
    for plane in X.read(path):
        meta = plane["event_metadata"]
        dev = R.DEVICE_PLANE.match(plane["name"])
        for line in plane["lines"]:
            if dev and line["name"] == R.OPS_LINE:
                devices.setdefault(int(dev.group(2)), []).extend(
                    (scope_of(meta[mid].get("tf_op")), s, s + d)
                    for mid, s, d in line["events"])
            elif plane["name"] == R.HOST_PLANE:
                host.extend((meta[mid]["name"], s, s + d)
                            for mid, s, d in line["events"])
    wins = R.spans_named(host, window)
    if not wins:
        return None
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    searches = R.clip(R.spans_named(host, search), lo, hi)
    spans = [(n, s, e) for n, s, e in host
             if n.startswith(PREFIX) and n not in ENVELOPE]
    scope_ns, idle_ns, busy, n_dev = {}, {}, 0.0, 0
    for events in devices.values():
        if not any(min(e, hi) > max(s, lo) for _, s, e in events):
            continue
        n_dev += 1
        for s0, s1 in searches:
            inside = [(n, max(s, s0), min(e, s1)) for n, s, e in events
                      if min(e, s1) > max(s, s0)]
            for n, ns in R.self_times(inside).items():
                scope_ns[n] = scope_ns.get(n, 0.0) + ns
            merged = R.merge([(s, e) for _, s, e in inside])
            busy += R.total(merged)
            for n, ns in span_idle(R.complement(merged, s0, s1),
                                   spans).items():
                idle_ns[n] = idle_ns.get(n, 0.0) + ns
    n_dev = max(n_dev, 1)
    scoped = any(k != UNSCOPED for k in scope_ns)
    return {"searches": len(searches),
            "scope_s": {k: v / 1e9 / n_dev for k, v in scope_ns.items()},
            "idle_s": {k: v / 1e9 / n_dev for k, v in idle_ns.items()},
            "search_busy_s": busy / 1e9 / n_dev,
            "search_idle_s": sum(idle_ns.values()) / 1e9 / n_dev,
            # does any device operation of the searches carry a scope,
            # and does the trace hold ANY name of the program's?
            "scoped": scoped, "named": scoped or bool(spans)}


def of_run(run):
    """The analysis of a traced run (`run` as run.py hands it to a reader),
    or None: no trace, no search traced, or no `jaxmc.*` name in it — the
    program as it was before it named anything."""
    trace_dir = (run.get("out") or {}).get("trace_dir")
    if not trace_dir:
        return None
    import reduce
    path = reduce.newest_xplane(trace_dir)
    an = analyze(path) if path else None
    return an if an and an["named"] and an["searches"] else None


def device_s(run, scopes):
    """Device self seconds per search under the given scopes; None where no
    operation of the searches carries any scope (executables from a cache
    filled before the scopes existed)."""
    an = of_run(run)
    return None if an is None or not an["scoped"] else \
        sum(an["scope_s"].get(s, 0.0) for s in scopes) / an["searches"]


def idle_s(run, spans):
    """Device-idle seconds per search under the given program spans
    (`jaxmc.<span>` names, or "unattributed")."""
    an = of_run(run)
    return None if an is None else \
        sum(an["idle_s"].get(s, 0.0) for s in spans) / an["searches"]


def unscoped_share(run):
    """Per cent of the searches' device-busy time under no scope."""
    an = of_run(run)
    if an is None or an["search_busy_s"] <= 0:
        return None
    return 100.0 * an["scope_s"].get(UNSCOPED, 0.0) / an["search_busy_s"]


if __name__ == "__main__":
    import json
    target = sys.argv[1]
    if os.path.isdir(target):
        import reduce
        target = reduce.newest_xplane(target)
    print(json.dumps(analyze(target), indent=1))
