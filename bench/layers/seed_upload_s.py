"""Host seconds per search inside the calls that hand the seed to the device,
up to their RETURN: the rise inside the window of the program's float counter
`seed.upload_s` (`jnp.asarray` of the host-built tables, the mesh's `_put`s,
`_device_table` under a cap, the scalar operands) over the searches
(SPANS.records.md).  The uploads are asynchronous: what the device still
waits for after the call returned is `dispatch_idle_s`'s, not this.  None
where the program has no such counter (before PR 34)."""


def read(run):
    art = (run.get("out") or {}).get("artifacts") or {}
    try:
        a, b = (art[k]["counters"] for k in ("at_window", "after"))
        rise = b["seed.upload_s"] - a.get("seed.upload_s", 0.0)
    except (KeyError, TypeError):
        return None
    return rise / art["searches"] if art.get("searches") else None
