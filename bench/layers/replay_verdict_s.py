"""Client wall, POST sent to result read back, of a re-run's job (a
byte-identical resubmission: warm engine, replay of the finalized
checkpoint, no search) under the window's load; mean over the window's
replayed jobs."""

import served


def read(run):
    return served.mean_of(j["client_s"]
                          for j in served.jobs(run, "rerun"))
