"""Wall of the `search` request of a searched job (the whole of
`explore()`: seed, dispatches with the first one's cache load, fetches, the
finalized checkpoint), from its artifact; mean over the window's searched
jobs."""

import served


def read(run):
    return served.per_searched_job(run, served.phase_s(("search",)))
