"""Seconds from a searched job's start in the owner to a built engine: the
spans `load` + `parse` + `analyze` + `engine_build` of the job's own
recorder, from the artifact the client read back; mean over the window's
searched jobs.  `build_s.desk` once per commit and cfg."""

import served


def read(run):
    return served.per_searched_job(
        run, served.phase_s(("load", "parse", "analyze", "engine_build")))
