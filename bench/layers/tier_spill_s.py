"""Host seconds per search spent spilling: the rise inside the window of the
wall under the program span `tier.spill` (the whole device table to the
host, its sorted valid prefix admitted as one cold run, an empty table
uploaded in its place), over the searches (SPANS.ooc.md).  None where the
run never spilled."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["phases"] for k in ("at_window", "after"))
    except (KeyError, TypeError):
        return None
    if not art.get("searches") or "tier.spill" not in b:
        return None
    return (b["tier.spill"] - a.get("tier.spill", 0.0)) / art["searches"]
