"""Seconds from entry to a built engine: spans load + parse + engine_build of
the session's own telemetry (host clock, in the program), chip init apart
(`device_init_s.desk`).  Moves `setup_s`."""


def read(run):
    ph = run["out"]["artifacts"]["at_window"]["phases"]
    return sum(ph.get(k, 0.0) for k in ("load", "parse", "engine_build"))
