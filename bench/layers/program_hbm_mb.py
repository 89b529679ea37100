"""MB of device memory the largest program dispatched needs while it runs, per
device: the program gauge `program.hbm_bytes` at the end of the window —
arguments + outputs − aliased (donated) bytes + temporaries of
`memory_analysis()` of the executable the process itself compiled or loaded
(`obs/prof.py`).  The number to hold against the chip's 16 GB, beside
`hbm_peak_mb` (SPANS.records.md).  None where the program set no such gauge
(before PR 34)."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        value = art["after"]["gauges"].get("program.hbm_bytes")
    except (KeyError, TypeError, AttributeError):
        return None
    return None if value is None else value / 1e6
