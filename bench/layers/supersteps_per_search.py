"""Dispatches of the sharded engine's superstep program per whole search:
the rise of the program counter `mesh.host_syncs` (one scalar-ring drain a
superstep) inside the window over the searches.  The true dispatch count of
the mesh cell (`dispatches_per_search` counts the sites `bfs.*`).  None
where the program has no such counter."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        rise = b["mesh.host_syncs"] - a.get("mesh.host_syncs", 0)
    except (KeyError, TypeError):
        return None
    return rise / art["searches"] if art.get("searches") else None
