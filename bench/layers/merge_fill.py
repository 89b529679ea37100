"""Per cent of the seen-table slots the merge built in the window that took
a new row: the rise of the program counters `search.rows_new` over
`search.slots_merged` (blocks of the merged table `_rank_merge` built x
the block's rows, a level; summed over the shards on the mesh).
`seen_fill` with the build's denominator: the merge's tail is sized by what
is live where this reads far above `seen_fill`.  None where the program has
no such counter (before PR 29) or built nothing."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        new, slots = (b[k] - a.get(k, 0) for k in
                      ("search.rows_new", "search.slots_merged"))
    except (KeyError, TypeError):
        return None
    return 100.0 * new / slots if slots else None
