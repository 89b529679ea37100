"""Device self seconds per search under the scope `jaxmc.trace.walk`: the
backward walk over the state log — the logged levels expanded again, the
successors packed and compared with the target, the result block — from the
traced searches (bench/spans.py; the walk's expansion carries the walk's
scope, not `jaxmc.expand`).  None where the program has no such scope
(before PR 44)."""

import spans

SCOPE = "jaxmc.trace.walk"


def read(run):
    an = spans.of_run(run)
    seconds = an and an["scope_s"].get(SCOPE)
    return seconds / an["searches"] if seconds else None
