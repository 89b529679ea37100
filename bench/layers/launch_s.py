"""Host seconds per search inside the jitted entry points' calls, up to their
RETURN: the rise inside the window of the program's float counter
`dispatch.launch_s` (`obs/prof.py` `Profiler.record`: `time.perf_counter`
around `fn(*args)` at every dispatch site — the search programs and the small
host-boundary ones, `bfs.host_keys`, `bfs.packed_keys`) over the searches
(SPANS.records.md).  The enqueue, not the device's work: `search.dispatch`
goes on to `block_until_ready`.  None where the program has no such counter
(before PR 34)."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        rise = b["dispatch.launch_s"] - a.get("dispatch.launch_s", 0.0)
    except (KeyError, TypeError):
        return None
    return rise / art["searches"] if art.get("searches") else None
