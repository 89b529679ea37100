"""Members a vbatch: `batch_occupancy` of the `serve` block (the widest
dispatch of the run), mean over the distinct runs that answered the window's
jobs, a job that ran alone counting 1.  The matrix's width where every
commit ran as one cohort."""

import cohorts


def read(run):
    vb = cohorts.vbatches(run)
    return sum(b["occupancy"] for b in vb) / len(vb) if vb else None
