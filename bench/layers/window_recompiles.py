"""Compilations inside the measured window: the larger of the rise of
`compile.xla_compiles` and the number of dispatches flagged
`fresh_compile`.  0 is the only healthy value."""


def read(run):
    art = run["out"]["artifacts"]
    a, b = art["at_window"], art["after"]
    xla = b["counters"].get("compile.xla_compiles", 0) \
        - a["counters"].get("compile.xla_compiles", 0)
    return max(xla, b["fresh_compiles"] - a["fresh_compiles"])
