"""What the served path adds to a searched job: the client's wall from the
POST sent to the result read back, minus the owner's own `job_wall_s` in the
artifact: HTTP, lint, signature, the spool's hard writes, the wait in the
queue and for the owner's pipe, polling; mean over the window's searched
jobs."""

import served


def read(run):
    return served.per_searched_job(
        run, lambda j: None if j["serve"].get("job_wall_s") is None
        else j["client_s"] - j["serve"]["job_wall_s"])
