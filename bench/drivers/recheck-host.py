"""Driver for a `recheck` cell that takes a WHOLE four-chip host for
steadiness alone (`chips: 4` in its entry, the search on one chip): the
`recheck` driver, unchanged, on the chip.  `recheck` asks for as many devices
as the cell has chips; on the chip the host has them.  In a CPU rehearsal
XLA:CPU has one, so this driver asks it for as many as the cell's chips
before jax comes up, and hands over."""

from __future__ import annotations

import os

from lib import load_module


def run(ctx: dict) -> dict:
    if ctx["rehearsal"]:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count="
            f"{ctx['cell']['chips']}").strip()
    recheck = load_module(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "recheck.py"), "bench_driver_recheck")
    return recheck.run(ctx)
