"""Device-idle seconds per search while the host is in `search.dispatch`
(resident) or `level.dispatch` (level engine): launch latency and the wait
for operands still on their way.  The seed's uploads are asynchronous, so
the device's wait for the seen table and the frontier is paid HERE, not
under `search.seed` (bench/spans.py)."""

import spans


def read(run):
    return spans.idle_s(run, ("jaxmc.search.dispatch",
                              "jaxmc.level.dispatch"))
