"""Programs the owner COMPILED inside the window: the `prof.programs` records
of the window's jobs whose `origin` is `compiled` and not `loaded`
(`obs/prof.py`: loaded = `compile.persistent_cache_hits` rose around the call
that made the executable).  A new signature builds a new engine that asks for
its executables again, so every searched job loads; with the checkout's cache
warm none compiles: 0 is the only healthy value.  (`window_recompiles` is a
session's own count and does not apply to a cell whose every job is a new
session.)  None where the artifacts hold no program records."""

import served


def read(run):
    js = served.jobs(run)
    if not js or not any("compiled" in j for j in js):
        return None
    return sum(j.get("compiled", 0) for j in js)
