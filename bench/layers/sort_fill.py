"""Per cent of the slots the engines sorted in the window that held a
generated state: the rise of the program counters `search.rows_valid` over
`search.slots_sorted` (AccCap a level on the resident engine, the candidate
block A x FC a level on the level engine).  Work against capacity, counted
where it happens.  None where the program has no such counters."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        valid, slots = (b[k] - a.get(k, 0) for k in
                        ("search.rows_valid", "search.slots_sorted"))
    except (KeyError, TypeError):
        return None
    return 100.0 * valid / slots if slots else None
