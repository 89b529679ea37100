"""Per cent of the traced searches' device-busy time under NO `jaxmc.*`
scope: how far to trust the five `*_device_s`.  100 means the executables
came from a compile cache filled before the scopes existed (bench/spans.py).
"""

import spans


def read(run):
    return spans.unscoped_share(run)
