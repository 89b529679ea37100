"""MB of the engine's capacity-sized device tables at the capacities in
force at the end of the window: the program gauge `search.table_bytes`,
defined per engine (seen, frontier and, in the resident engine, the level
accumulator's keys and rows; on the mesh all shards and the trace ring),
from the tables' shapes: compare a cell with itself, not engines.  Beside `hbm_peak_mb` it says how much of the peak is
capacity (SPANS.deep.md).  None where the program set no such gauge (before
PR 30)."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        value = art["after"]["gauges"].get("search.table_bytes")
    except (KeyError, TypeError, AttributeError):
        return None
    return value / 1e6 if value else None
