"""Per cent of the index slots the merge's compaction of a level's NEW KEYS
gathered in the window that held one: the rise of the program counter
`search.rows_new` over `search.slots_keyed` (blocks of QB =
`bfs._probe_block_rows(AccCap)` index slots the gather of the new keys ran, x
QB; a level with n new keys runs ceil(n / QB) of them).  Since PR 48 a
resident program with more key slots than `bfs._BUILD_WHOLE_KEYS` (2^21)
compacts the new keys once a level and a block of the seen table's build
reads a slice of them (gauge `merge.build_form` = `window`).  Near 100 the
compaction touched the keys that are new and little else — one partly
filled block a level — where the build it replaced fetched an index for
every row it wrote, 10-13 in a hundred of them new.  None where the program
has no such counter: before PR 48, on the level engine and the mesh, in a
resident program of no more key slots than that (`merge.build_form` =
`whole`), or where nothing was gathered."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        rows, slots = (b[k] - a.get(k, 0) for k in
                       ("search.rows_new", "search.slots_keyed"))
    except (KeyError, TypeError):
        return None
    return 100.0 * rows / slots if slots else None
