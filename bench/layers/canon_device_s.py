"""Device self seconds per search under the scope `jaxmc.canon`: the cfg
SYMMETRY canonicaliser in front of every dedup key (compile/symmetry2.py),
from the traced searches (bench/spans.py; the canonicaliser's operations
carry its own scope inside `jaxmc.keys`, so `expand_device_s` does not count
them).  None where no operation of the searches carries the scope: the
program before PR 47, a cfg without SYMMETRY, or a network XLA fused into
the key fusion (bench/SPANS.symmetry.md)."""

import spans

SCOPE = "jaxmc.canon"


def read(run):
    an = spans.of_run(run)
    seconds = an and an["scope_s"].get(SCOPE)
    return seconds / an["searches"] if seconds else None
