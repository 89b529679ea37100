"""`compile.xla_compile_s` of a searched job's artifact: seconds inside
jax's backend compile, which with a warm persistent cache is the time to
LOAD the job's programs (a new signature builds a new engine, and a new
engine asks for its executables again); mean over the window's searched
jobs.  Most of it falls inside the job's first `search.dispatch`."""

import served


def read(run):
    return served.per_searched_job(
        run, lambda j: j["counters"].get("compile.xla_compile_s"))
