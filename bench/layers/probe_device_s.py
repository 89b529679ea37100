"""Device self seconds per search under `jaxmc.merge.probe`: the binary
searches of the candidate keys in the seen table (`_seen_probe`,
`_lower_bound`) (bench/spans.py)."""

import spans


def read(run):
    return spans.device_s(run, ("jaxmc.merge.probe",))
