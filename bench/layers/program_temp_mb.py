"""MB of XLA temporaries of the largest program dispatched, per device: the
program gauge `program.temp_bytes` at the end of the window — the
`temp_size_in_bytes` of `memory_analysis()` of the executable the process
itself compiled or loaded (`obs/prof.py`, one record per executable; the
largest by arguments + outputs − aliases + temp).  What `hbm_peak_mb`
(`memory_stats`) never shows (SPANS.records.md).  None where the program set
no such gauge (before PR 34, or a backend that keeps no executable to
read)."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        value = art["after"]["gauges"].get("program.temp_bytes")
    except (KeyError, TypeError, AttributeError):
        return None
    return None if value is None else value / 1e6
