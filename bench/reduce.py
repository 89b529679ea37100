"""From a profiler trace (`.xplane.pb`) to busy / idle seconds, seconds per
device operation and named idle gaps.  The only reader of traces in the
repo; kept with the benchmark so that every PR computes these numbers the
same way.  jax is imported only to PARSE the file (`ProfileData`), never a
backend.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Ops` carries one event per executed HLO
operation — nested where an operation (`while`, `conditional`, a fusion's
caller) contains others — and one plane `/host:CPU` with a line per host
thread, where `jax.profiler.TraceAnnotation` spans (`bench.*`) sit beside
the runtime's own events.  All `start_ns` share one clock.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: gaps shorter than this, and all but the NAMED_GAPS longest, are summed
#: under "<span>:short-gaps" and not named one by one
NAMED_GAP_NS = 50_000
NAMED_GAPS = 300
_SUFFIX = re.compile(r"(\.\d+)+$")
_LAYOUT = re.compile(r"\{[^}]*\}")
_HLO = re.compile(r"^%?([\w.\-]+) = (\([^)]*\)|\S+) ")


def op_name(name: str) -> str:
    """A stable label for one kind of operation.  A device event is named by
    its whole HLO instruction (`%fusion.12 = s32[4096,2]{1,0:T(8,128)}
    fusion(...), kind=kLoop, ...`): keep the name without its numbering and
    the result's shape without its layout, `fusion s32[4096,2]`, because the
    shape says which table an anonymous fusion or sort works on."""
    flat = _LAYOUT.sub("", name)
    m = _HLO.match(flat)
    if m:
        base, shape = m.group(1), m.group(2).replace(" ", "")
    else:
        base, shape = flat.split(" ")[0].lstrip("%"), ""
    return (f"{_SUFFIX.sub('', base)} {shape}".strip() or name)[:64]


def merge(intervals):
    """Union of [start, end) intervals as a sorted disjoint list."""
    out = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def complement(merged, lo, hi):
    gaps, cur = [], lo
    for a, b in merged:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def self_times(events):
    """Seconds per operation name with nested children taken out of their
    parent: `events` are (name, start, end) of ONE line."""
    out = {}
    stack = []           # [name, start, end, seconds of children]

    def pop():
        name, s, e, kids = stack.pop()
        out[name] = out.get(name, 0.0) + max(0.0, (e - s) - kids)
        if stack:
            stack[-1][3] += e - s

    for name, s, e in sorted(events, key=lambda t: (t[1], -t[2])):
        while stack and s >= stack[-1][2]:
            pop()
        stack.append([name, s, min(e, stack[-1][2]) if stack else e, 0.0])
    while stack:
        pop()
    return out


def _events(line):
    return [(ev.name, float(ev.start_ns), float(ev.start_ns)
             + float(ev.duration_ns)) for ev in line.events]


def read_planes(path: str):
    """{"devices": {ordinal: [(name, s, e)]}, "host": [(name, s, e)]}"""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(int(m.group(2)), []).extend(
                        _events(line))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(_events(line))
    return {"devices": devices, "host": host}


def spans_named(host, name):
    return [(s, e) for n, s, e in host if n == name]


def span_over(t, spans) -> str:
    """The innermost (shortest) bench.* span that covers time t."""
    cover = [(e - s, n) for n, s, e in spans if s <= t < e]
    return min(cover)[1] if cover else "outside-window"


def name_gap(gap, spans, others):
    """`<innermost bench span over the gap>:<what the host was doing>`.
    The host event is the SHORTEST one that covers at least half the gap;
    failing that, the one that overlaps it most (at least a tenth)."""
    a, b = gap
    width = b - a
    label = span_over((a + b) / 2, spans)
    best, best_key = None, None
    for n, s, e in others:
        ov = min(e, b) - max(s, a)
        if ov <= 0 or e <= s:
            continue
        key = (0, e - s) if ov >= width / 2 else \
            ((1, -ov) if ov >= width / 10 else None)
        if key is not None and (best_key is None or key < best_key):
            best, best_key = n, key
    return f"{label}:{op_name(best)}" if best else label


def reduce_trace(path: str, window: str = "bench.window",
                 search: str = "bench.search", top: int = 10):
    """The traced window reduced.  `busy_s` is the union of device-op
    intervals inside the window, averaged over the chips that ran
    anything; `search_busy_s` the same inside the `bench.search` spans."""
    planes = read_planes(path)
    host, devices = planes["host"], planes["devices"]
    wins = spans_named(host, window)
    if not wins:
        return None
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    searches = clip(spans_named(host, search), lo, hi)
    busy, search_busy, ops, gaps = [], [], {}, []
    for events in devices.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if min(e, hi) > max(s, lo)]
        if not inside:
            continue
        merged = merge([(s, e) for _, s, e in inside])
        busy.append(total(merged))
        search_busy.append(sum(total(clip(merged, s, e))
                               for s, e in searches))
        for n, sec in self_times(inside).items():
            ops[op_name(n)] = ops.get(op_name(n), 0.0) + sec
        gaps.extend(complement(merged, lo, hi))
    n_dev = max(len(busy), 1)
    spans = [(n, s, e) for n, s, e in host if n.startswith("bench.")]
    others = [(n, s, e) for n, s, e in host
              if not n.startswith("bench.") and min(e, hi) > max(s, lo)]
    named = {}
    gaps.sort(key=lambda g: g[0] - g[1])
    for rank_, gap in enumerate(gaps):
        width = gap[1] - gap[0]
        if width >= NAMED_GAP_NS and rank_ < NAMED_GAPS:
            label = name_gap(gap, spans, others)
        else:
            label = span_over((gap[0] + gap[1]) / 2, spans) + ":short-gaps"
        named[label] = named.get(label, 0.0) + width
    rank = lambda d: [[k, v / 1e9 / n_dev] for k, v in  # noqa: E731
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / n_dev / 1e9,
            "search_busy_s": sum(search_busy) / n_dev / 1e9,
            "searches_traced": len(searches),
            "devices_traced": len(busy),
            "device_ops": rank(ops), "idle_gaps": rank(named)}


def newest_xplane(trace_dir: str):
    import glob
    import os
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def raw_top(path: str, n: int = 30):
    """The n device operations with most total time under their FULL names,
    with the stats of one occurrence: for looking at a trace by hand."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    acc = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a = acc.setdefault(ev.name, [0.0, 0, None])
                a[0] += float(ev.duration_ns)
                a[1] += 1
                if a[2] is None:
                    a[2] = {k: (v if not isinstance(v, (bytes, str))
                                or len(v) < 300 else v[:300])
                            for k, v in ev.stats}
    return [[name, a[0] / 1e9, a[1], a[2]] for name, a in
            sorted(acc.items(), key=lambda kv: -kv[1][0])[:n]]


if __name__ == "__main__":
    import json
    import os
    import sys
    target = sys.argv[1]
    if os.path.isdir(target):
        target = newest_xplane(target)
    print(json.dumps(reduce_trace(target), indent=1))
    if len(sys.argv) > 2:
        for row in raw_top(target, int(sys.argv[2])):
            print(json.dumps(row, default=str))
