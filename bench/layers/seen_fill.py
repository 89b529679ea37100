"""Per cent of the seen-table rows the engines rewrote in the window that
admitted a new state: the rise of the program counters `search.rows_new`
(the distinct states the levels added) over `search.seen_slots` (`SC` a
level: the merge writes the whole table whatever the level found).  The
capacity ratio behind `scatter_device_s`.  None where the program has no
such counters."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        new, slots = (b[k] - a.get(k, 0) for k in
                      ("search.rows_new", "search.seen_slots"))
    except (KeyError, TypeError):
        return None
    return 100.0 * new / slots if slots else None
