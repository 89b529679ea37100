"""Per cent of a search's generated states that were generated TWICE: the
rise inside the window of the program counter `tier.redone_rows` (the
candidates of every level a spill rolled back and ran again) over the
reference's `generated` times the searches.  What the spill-and-redo rule
costs the device.  None where the program has no such counter (before
PR 32) or never spilled (SPANS.ooc.md)."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        redone = b["tier.redone_rows"] - a.get("tier.redone_rows", 0)
        generated = art["reference"]["generated"] * art["searches"]
    except (KeyError, TypeError):
        return None
    return 100.0 * redone / generated if generated else None
