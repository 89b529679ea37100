"""Host seconds per search of the counterexample's reconstruction: the
program span `search.trace` (and inside it `trace.walk`: the dispatch and
the one fetch, `trace.decode`: rows to states and labels) less the device
time under it — the device-idle seconds under those spans in the traced
searches (bench/spans.py).  None where the program has no such span (before
PR 44)."""

import spans

SPANS = ("jaxmc.search.trace", "jaxmc.trace.walk", "jaxmc.trace.decode")


def read(run):
    an = spans.of_run(run)
    if an is None or not any(s in an["idle_s"] for s in SPANS):
        return None
    return sum(an["idle_s"].get(s, 0.0) for s in SPANS) / an["searches"]
