"""MB of host-built tables a whole search uploads at its start: the rise of
the program counter `search.seed_bytes` (the seen table and the frontier at
their full capacities, on the mesh all shards and the trace ring, as handed
to `jnp.asarray` / `_put` under the `search.seed` span) inside the window
over the searches.  What scale adds to a search's fixed cost (SPANS.deep.md).
None where the program has no such counter (before PR 30)."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        rise = b["search.seed_bytes"] - a.get("search.seed_bytes", 0)
    except (KeyError, TypeError):
        return None
    return rise / 1e6 / art["searches"] if art.get("searches") else None
