"""Host seconds per search that BUILD the seed: the rise inside the window of
the program's float counters `seed.keys_s` (`_host_keys`, the mesh's owner
hash, the `lexsort`s) and `seed.tables_s` (every host-built table: `np.full`
and its fill, `_init_shards`, the mesh's trace ring) over the searches.  Taken
with `time.perf_counter` where the work happens, under the span `search.seed`
and taking nothing out of it (SPANS.records.md): the program's own clock,
traced run or not.  With `seed_upload_s` it is `search.seed`'s wall.  None
where the program has no such counters (before PR 34)."""

COUNTERS = ("seed.keys_s", "seed.tables_s")


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
    except (KeyError, TypeError):
        return None
    if not art.get("searches") or not any(c in b for c in COUNTERS):
        return None
    return sum(b.get(c, 0.0) - a.get(c, 0.0) for c in COUNTERS) \
        / art["searches"]
