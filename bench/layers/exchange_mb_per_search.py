"""MB the exchange moves per whole search over the whole mesh, the chips'
own buckets included: the rise of the program counter `mesh.exchange_bytes`
(levels x D x D x (B + SB) rows, from the static shapes) inside the window
over the searches.  None where the program has no such counter."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        rise = b["mesh.exchange_bytes"] - a.get("mesh.exchange_bytes", 0)
    except (KeyError, TypeError):
        return None
    return rise / 1e6 / art["searches"] if art.get("searches") else None
