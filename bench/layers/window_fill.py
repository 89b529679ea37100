"""Per cent of the query slots the seen-table probe searched in the window
whose block searched a WINDOW of the table and not the whole of it: the
rise of the program counters `search.slots_windowed` over
`search.slots_probed` (blocks of sorted candidate keys x the block's rows;
a block takes the window where the answers of its first and its last live
query lie under W = `bfs._probe_window_rows(SC)` rows apart).  Near 100 the
probe's gathers read a copy small enough for the chip's fast memory; the
rest paid the whole table's price.  None where the program has no such
counter: before PR 45, on the level engine and the mesh, and on a resident
program whose table is no larger than W (it has no window to count), or
where nothing was searched."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        windowed, slots = (b[k] - a.get(k, 0) for k in
                           ("search.slots_windowed", "search.slots_probed"))
    except (KeyError, TypeError):
        return None
    return 100.0 * windowed / slots if slots else None
