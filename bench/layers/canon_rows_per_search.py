"""Rows the SYMMETRY canonicaliser took, per search: the rise of the program
counter `search.canon_rows` inside the window (every generated state once:
the successors on the device, the initial states on the host's side of the
same function) over the searches.  None where the program has no such
counter (before PR 47, or a cfg without SYMMETRY)."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        rise = b["search.canon_rows"] - a.get("search.canon_rows", 0)
    except (KeyError, TypeError):
        return None
    return rise / art["searches"] if art.get("searches") else None
