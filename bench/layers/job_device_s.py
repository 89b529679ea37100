"""Device-busy seconds per searched job inside the owner's `jaxmc.search`
spans (session.py's span round `explore()`), from the owner's trace cut by
that span (bench/served.py, bench/spans.py): what the chip works for a
served job.  The replays' searches dispatch nothing."""

import served


def read(run):
    an, n = served.owner_searches(run), len(served.jobs(run, "edit"))
    return an["search_busy_s"] / n if an and n else None
