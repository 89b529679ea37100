"""Largest seen shard over the mean shard at the end of the last search:
the program gauge `mesh.shard_balance` (1.0 = the owner hash spreads the
keys evenly; 1.0005 on the full rung at PR 21).  None where the program
set no such gauge."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        return art["after"]["gauges"].get("mesh.shard_balance")
    except (KeyError, TypeError, AttributeError):
        return None
