"""Host seconds a commit spends firing its supersteps that are NOT the
device's: the float counters `batch.stack_s` + `batch.unstack_s` and the span
`batch.dispatch` of the leader's artifact (the host stacks the pending
chunks, uploads them, calls the vmapped program, fetches every output and
hands the slices out: a synchronous round trip under the dispatcher's lock)
less the device's busy seconds for those dispatches in the owner's trace
(`vstep_device_s`); per window commit.  None without a trace or where the
program has no such spans (before PR 39)."""

import cohorts


def read(run):
    fire = cohorts.per_commit(run, cohorts.fire_s)
    dev = cohorts.vstep_device_s(run)
    return None if fire is None or dev is None else fire - dev
