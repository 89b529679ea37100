"""`compile.xla_compile_s` up to the window's start: in a run whose programs
all hit the persistent cache it is the time to load them; in a checkout's
first run it is the compile time (the driver keeps that run apart)."""


def read(run):
    return run["out"]["artifacts"]["at_window"]["counters"].get(
        "compile.xla_compile_s")
