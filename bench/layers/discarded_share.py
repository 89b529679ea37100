"""Per cent of the rows that entered the seen table in the window that the
cfg's CONSTRAINT discarded: the rise of the program counter
`search.rows_discarded` over the rise of (`search.rows_new` +
`search.rows_discarded`) plus the initial states of every search (which the
host seeds: the reference computed in this run says how many).  It is the
MODEL's number, not the engine's (35.65 in `desk-constraint-4p`): it says
that the cfg still discards, and how much of the seen table's traffic the
branch decides on.  None where the program has no such counter: before
PR 51, or a cfg without a CONSTRAINT."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        new, gone = (b[k] - a.get(k, 0) for k in (
            "search.rows_new", "search.rows_discarded"))
        entered = new + gone + \
            art["searches"] * art["reference"]["levels"][0][0]
    except (KeyError, TypeError, IndexError):
        return None
    return 100.0 * gone / entered if entered else None
