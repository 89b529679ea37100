"""Device-idle seconds per search while the host is in the program spans
`search.init` (initial states enumerated and filtered) and `search.seed`
(their keys, the host tables, the uploads) (bench/spans.py)."""

import spans


def read(run):
    return spans.idle_s(run, ("jaxmc.search.init", "jaxmc.search.seed"))
