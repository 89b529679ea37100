"""Host-to-device dispatches of the search programs per whole search: the
prof sites `bfs.resident_run` (a few levels per dispatch) and
`bfs.level_step` (one per level), counted inside the window."""

SITES = ("bfs.resident_run", "bfs.level_step")


def read(run):
    art = run["out"]["artifacts"]
    d0, d1 = art["at_window"]["dispatches"], art["after"]["dispatches"]
    n = sum(d1.get(s, 0) - d0.get(s, 0) for s in SITES)
    return n / art["searches"] if art["searches"] else None
