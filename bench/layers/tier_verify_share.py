"""Per cent of the keys the host looked up in cold runs that went past a
run's fence to the whole-row compare that decides, in the window: the rise of
the program counter `tier.keys_verified` over that of `tier.keys_probed`
(both published by `bfs._tier_probe`, one probe a level once a run exists).
Since PR 50 a cold probe meets a HOST run at its fence — the native column
of its keys' leading 8 bytes, searched natively — and only a query whose
leading bytes are in the run takes the 16-byte void-row search.
`keys_verified` sums those over the runs a probe searched (a disk run has no fence: every query
counts), so the share reads a little over the share of the probed keys that
were cold — in the cell 0.736 for 0.670: 9,852 of 1,470,128 keys are cold
and 968 of them sit in two runs when they are asked for, 10,820 passes — and
more only where leading 8 bytes collide; it can pass 100 where every query
passes every fence (SPANS.coldprobe.md).  None where the program
has no such counter: before PR 50, without a cap, or in a search that never
spilled."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        verified, probed = (b[k] - a.get(k, 0) for k in
                            ("tier.keys_verified", "tier.keys_probed"))
    except (KeyError, TypeError):
        return None
    return 100.0 * verified / probed if probed else None
