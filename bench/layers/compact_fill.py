"""Per cent of the slots the resident level's compaction gathered in the
window that held a new row: the rise of the program counter
`search.rows_new` over `search.slots_compacted` (blocks of RB =
`bfs._compact_block_rows(AccCap, FCap)` index slots the gathers of the new
rows ran, x RB; a level with n new rows runs ceil(n / RB) of them, and where
the cfg has a CONSTRAINT as many again for the rows it keeps).  Near 100 the
compaction touched the rows that exist and little else — one partly filled
block a level; up to PR 45 it touched every AccCap slot a level (6.8-8.6 per
cent of them held a new row in the benchmark's cells).  None where the
program has no such counter: before PR 46, on the level engine and the mesh,
or where nothing was gathered."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        rows, slots = (b[k] - a.get(k, 0) for k in
                       ("search.rows_new", "search.slots_compacted"))
    except (KeyError, TypeError):
        return None
    return 100.0 * rows / slots if slots else None
