"""Device self seconds per search under the scopes `jaxmc.expand` (row
unpack, the (state x action) expansion, the POR mask) and `jaxmc.keys`
(pack + fingerprint), from the traced searches (bench/spans.py)."""

import spans


def read(run):
    return spans.device_s(run, ("jaxmc.expand", "jaxmc.keys"))
