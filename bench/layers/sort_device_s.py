"""Device self seconds per search under `jaxmc.merge.sort`: the sort of the
level's candidate keys inside `_rank_merge` (bench/spans.py)."""

import spans


def read(run):
    return spans.device_s(run, ("jaxmc.merge.sort",))
