"""Device-busy seconds a commit spends in its vmapped dispatches: the traced
window's device-busy seconds over the `jaxmc.batch.dispatch` spans inside it
(bench/cohorts.py `dispatch_device_s`: by the spans' COUNT, not under their
borders — the trace's device clock runs milliseconds off the host's), times
the dispatches a commit makes.  None without a trace or where the program
has no such span (before PR 39)."""

import cohorts


def read(run):
    return cohorts.vstep_device_s(run)
