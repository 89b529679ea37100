"""Chips that ran any operation inside the traced window
(`devices_traced` of bench/reduce.py): 4 in the four-chip cell, or the
deployment's guarantee — every chip owns a shard — is broken.  None where
the trace shows no device at all (an XLA:CPU rehearsal)."""


def read(run):
    tr = run.get("trace")
    return (tr or {}).get("devices_traced") or None
