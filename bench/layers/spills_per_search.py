"""Spills per whole search: the rise of the program counter `tier.spills`
(one per sorted prefix admitted to the cold tiers) inside the window over
the searches.  Each spill rolls a level back and runs it again.  None where
the program never spilled (SPANS.ooc.md)."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        rise = b["tier.spills"] - a.get("tier.spills", 0)
    except (KeyError, TypeError):
        return None
    return rise / art["searches"] if art.get("searches") else None
