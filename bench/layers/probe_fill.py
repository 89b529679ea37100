"""Per cent of the query slots the seen-table probe searched in the window
that held a generated state: the rise of the program counters
`search.rows_valid` over `search.slots_probed` (blocks of sorted candidate
keys the binary searches visited x the block's rows, a level; summed over
the shards on the mesh).  `sort_fill` with the probe's denominator: the
probe is sized by what is live where this reads far above `sort_fill`.
None where the program has no such counter (before PR 27) or searched
nothing."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        valid, slots = (b[k] - a.get(k, 0) for k in
                        ("search.rows_valid", "search.slots_probed"))
    except (KeyError, TypeError):
        return None
    return 100.0 * valid / slots if slots else None
