"""`compile.xla_compile_s` of the first-contact job's artifact: seconds in
XLA's compiler, the bulk of a first contact."""


def read(run):
    return run["out"]["artifacts"]["job"]["counters"].get(
        "compile.xla_compile_s")
