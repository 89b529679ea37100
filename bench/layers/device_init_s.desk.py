"""Seconds of the session's `device_init` span: jax import, the compile
cache's guard and the chip coming up.  Only the runtime can shorten it."""


def read(run):
    return run["out"]["artifacts"]["at_window"]["phases"].get("device_init")
