"""Device-idle seconds per search while the host reads results back: the
program spans `search.fetch` (resident), `level.sync` and `level.rows`
(level engine) (bench/spans.py)."""

import spans


def read(run):
    return spans.idle_s(run, ("jaxmc.search.fetch", "jaxmc.level.sync",
                              "jaxmc.level.rows"))
