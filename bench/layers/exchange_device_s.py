"""Device self seconds per search under `jaxmc.mesh.route` (owner hash,
bucket placement, spill selection) and `jaxmc.mesh.exchange` (the
`all_to_all`s or `all_gather`, the spill pass), averaged over the chips
that ran anything (bench/spans.py).  None where no operation of the
searches carries either scope: a one-chip engine, or executables from a
cache filled before the scopes existed."""

import spans

SCOPES = ("jaxmc.mesh.route", "jaxmc.mesh.exchange")


def read(run):
    an = spans.of_run(run)
    if an is None or not any(s in an["scope_s"] for s in SCOPES):
        return None
    return spans.device_s(run, SCOPES)
