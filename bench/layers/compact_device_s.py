"""Device self seconds per search under `jaxmc.compact` (the gathers of the
new rows, the candidate and frontier compaction sorts) and `jaxmc.scan`
(constraint and invariant predicates) (bench/spans.py)."""

import spans


def read(run):
    return spans.device_s(run, ("jaxmc.compact", "jaxmc.scan"))
