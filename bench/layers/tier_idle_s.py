"""Device-idle seconds per search while the host is in one of the five tier
spans: `tier.spill`, and under `search.fetch` `tier.pull`, `tier.keys`,
`tier.probe`, `tier.push` (bench/spans.py gives each piece of an idle gap to
the innermost span over it, so these seconds are NOT in `sync_idle_s`).
`tier.keys` runs a small device program of its own: its busy time is not
idle.  None where the program never opened such a span (before PR 32, or a
run that never spilled) (SPANS.ooc.md)."""

import spans

SPANS = ("tier.spill", "tier.pull", "tier.keys", "tier.probe", "tier.push")


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        phases = art["after"]["phases"]
    except (KeyError, TypeError):
        return None
    if not any(s in phases for s in SPANS):
        return None
    return spans.idle_s(run, tuple("jaxmc." + s for s in SPANS))
