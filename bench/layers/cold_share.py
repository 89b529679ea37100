"""Keys held in cold runs at the end of a search, per cent of the model's
distinct states: the program gauge `tier.occupancy` (its `host` + `disk`
entries when the last search of the window ended) over the reference's
`distinct`.  It counts KEYS IN RUNS: a cold duplicate the emptied table
re-admitted and a later spill carried out again sits in two runs and counts
twice, so this reads a little over the share of distinct states that are
cold (77.71 against 77.42 in the cell: 1,444,808 keys in runs, 1,439,416 of
them distinct, SPANS.ooc.md).  None where the program sets no such gauge
(no cap, or an engine that never had one)."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        occ = art["after"]["gauges"].get("tier.occupancy")
        distinct = art["reference"]["distinct"]
        cold = occ["host"] + occ["disk"]
    except (KeyError, TypeError, AttributeError):
        return None
    return 100.0 * cold / distinct if distinct else None
