"""Device-idle seconds per searched job inside the owner's `jaxmc.search`
spans: the chip waiting while a NEW engine's first dispatches trace, lower
and load their (cached) program, while the seed is built and the result
fetched, and under the finalized checkpoint's write (bench/served.py,
bench/spans.py).  With `job_device_s` it is `job_search_s`."""

import served


def read(run):
    an, n = served.owner_searches(run), len(served.jobs(run, "edit"))
    return an["search_idle_s"] / n if an and n else None
