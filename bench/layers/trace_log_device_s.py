"""Device self seconds per search under the scope `jaxmc.trace.log`: each
level's new frontier rows appended to the state log inside the resident
loop (one `dynamic_update_slice` a level and its bookkeeping), from the
traced searches (bench/spans.py).  None where the program has no such scope
(before PR 44)."""

import spans

SCOPE = "jaxmc.trace.log"


def read(run):
    an = spans.of_run(run)
    seconds = an and an["scope_s"].get(SCOPE)
    return seconds / an["searches"] if seconds else None
