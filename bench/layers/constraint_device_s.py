"""Device self seconds per search under the scope `jaxmc.constraint`: the
cfg's CONSTRAINT predicates over a level's new rows and what keeps the
discarded ones out of the frontier — on the resident engine the unpack of
the new rows, the predicates, the stable sort that names the kept rows first
and the kept rows' gather (bench/SPANS.constraint.md), from the traced
searches (bench/spans.py).  `compact_device_s` and the scan's seconds do not
count them: the reduction takes the innermost `jaxmc.*` component.  None
where no operation of the searches carries the scope: the program before
PR 51, or a cfg without a CONSTRAINT."""

import spans

SCOPE = "jaxmc.constraint"


def read(run):
    an = spans.of_run(run)
    seconds = an and an["scope_s"].get(SCOPE)
    return seconds / an["searches"] if seconds else None
