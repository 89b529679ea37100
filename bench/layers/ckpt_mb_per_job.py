"""Bytes of finalized checkpoint a searched job writes, ÷ 10^6: the job's
counter `checkpoint.bytes` (the size of every file `checkpoint.write`
wrote, after the rename: `engine/ckpt.py`, `bfs._write_ck`) — what
`job_ckpt_s` pickles, hashes and fsyncs; mean over the window's searched
jobs (a commit's bytes over its jobs in the cohort cell: every member
writes its own).  None where the program has no such counter (before
PR 49)."""

import served


def read(run):
    return served.per_searched_job(
        run, lambda j: None if j["counters"].get("checkpoint.bytes") is None
        else j["counters"]["checkpoint.bytes"] / 1e6)
