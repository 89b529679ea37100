"""Host seconds per search of the cold-tier filter at the level boundary:
the rise inside the window of the wall under the program spans `tier.pull`
(the device-new frontier to the host), `tier.keys` (its dedup keys,
`_packed_keys`), `tier.probe` (`TieredSeen.probe`: a binary search per cold
run) and `tier.push` (the filtered frontier rebuilt and uploaded), over the
searches (SPANS.ooc.md).  The program's own clock, traced run or not.  None
where the program has no such span (before PR 32) or never spilled."""

SPANS = ("tier.pull", "tier.keys", "tier.probe", "tier.push")


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["phases"] for k in ("at_window", "after"))
    except (KeyError, TypeError):
        return None
    if not art.get("searches") or not any(s in b for s in SPANS):
        return None
    return sum(b.get(s, 0.0) - a.get(s, 0.0) for s in SPANS) \
        / art["searches"]
