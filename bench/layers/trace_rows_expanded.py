"""Log rows the backward walks expanded again, per search: the rise of the
program counter `search.trace_rows_expanded` inside the window (rows of the
chunks each level's walk visited before it found a parent) over the
searches.  None where the program has no such counter (before PR 44)."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        rise = b["search.trace_rows_expanded"] \
            - a.get("search.trace_rows_expanded", 0)
    except (KeyError, TypeError):
        return None
    return rise / art["searches"] if art.get("searches") else None
