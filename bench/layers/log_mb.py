"""MB of state rows a search appended to its log on the device: the rise of
the program counter `search.log_bytes` inside the window (rows x state words
x 4 B: the initial frontier and every level the search went on from) over
the searches / 1e6.  The log TABLE's size is in `table_mb`.  None where the
program has no such counter (before PR 44)."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        rise = b["search.log_bytes"] - a.get("search.log_bytes", 0)
    except (KeyError, TypeError):
        return None
    return rise / 1e6 / art["searches"] if art.get("searches") else None
