"""Device self seconds per search under `jaxmc.merge.scatter`: the rest of
`_rank_merge` — ranks, compaction of the new keys, the write of the merged
seen table (bench/spans.py)."""

import spans


def read(run):
    return spans.device_s(run, ("jaxmc.merge.scatter",))
