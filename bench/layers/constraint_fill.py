"""Per cent of the slots the CONSTRAINT branch worked over in the window
that held a row to judge: the rise of the program counters
(`search.rows_new` + `search.rows_discarded`) — the rows that entered the
seen table, kept or discarded — over `search.slots_constrained` (the slots
the predicates and the sort ran over: levels run x AccCap on the resident
engine of PR 51).  Near 100 the branch touched the rows that exist and
little else.  None where the program has no such counters: before PR 51, or
a cfg without a CONSTRAINT."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        new, gone, slots = (b[k] - a.get(k, 0) for k in (
            "search.rows_new", "search.rows_discarded",
            "search.slots_constrained"))
    except (KeyError, TypeError):
        return None
    return 100.0 * (new + gone) / slots if slots else None
