"""`compile.xla_compiles` of the first-contact job's artifact."""


def read(run):
    return run["out"]["artifacts"]["job"]["counters"].get(
        "compile.xla_compiles")
