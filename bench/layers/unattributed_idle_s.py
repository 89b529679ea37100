"""Device-idle seconds per search inside `bench.search` under no program
span finer than the `search` envelope: what the spans do not explain yet.
Healthy is about 0 (bench/spans.py)."""

import spans


def read(run):
    return spans.idle_s(run, (spans.UNATTRIBUTED,))
