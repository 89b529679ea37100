r"""Device-agnostic backend layer (ISSUE 11).

`jaxmc/tpu/` (gone since ISSUE 43) grew three engines (bfs/mesh/multihost) that were TPU-named
but already ran anywhere XLA does; no round since r01 has produced a
real device number because the engine layer was welded to that name and
to whatever platform jax initialized first.  This package makes
{tpu, gpu, cpu-XLA} first-class:

  BackendDescriptor   the value the engines are parameterized over —
                      platform, device count, mesh shape, the donation
                      policy (XLA:CPU ignores donation, accelerators
                      want it) and the capacity-profile NAMESPACE, so
                      caps learned on one platform can never warm-start
                      a different one (an 8-chip TPU's per-shard caps
                      are nonsense on a 1-device CPU run).
  describe_backend()  build the descriptor for the LIVE jax backend
                      (call after device init).
  oracle              the preflight oracle (jaxmc/backend/oracle.py):
                      probes every visible platform with a tiny
                      compile+dispatch in a timeout-guarded subprocess
                      (a dead device must cost seconds, not a hung
                      run), picks the best live one, and
                      stamps the verdict + per-candidate probe walls
                      into telemetry (`backend.oracle_choice`).

The engines live in jaxmc/backend/{bfs,mesh,multihost}.py.  This
module itself never imports jax at import time — `python -m jaxmc.obs`
must keep working in an interp-only environment.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional, Tuple

#: platform preference for "best live backend": higher wins (matches
#: obs/report.py's demotion rank — a backend swap downward is a REGRESS)
PLATFORM_RANK = {"cpu": 1, "gpu": 2, "tpu": 3}

#: the selectable surface behind `--backend` (cli.py): "interp" and
#: "jax" keep their historical meaning; the platform names pin the jax
#: engine to one platform; "auto" asks the preflight oracle
BACKEND_CHOICES = ("interp", "jax", "auto", "cpu", "gpu", "tpu")


@dataclass(frozen=True)
class BackendDescriptor:
    """Everything an engine needs to know about the device layer it is
    compiled for.  One value, passed down instead of re-derived from
    global jax state in every engine, so bfs/mesh/multihost cannot
    disagree about the platform they are running on."""

    platform: str              # "cpu" | "gpu" | "tpu"
    device_count: int
    mesh_shape: Tuple[int, ...]  # (D,) — the 1-d "d" mesh axis
    donate: bool               # buffer-donation policy for jitted steps
    profile_ns: str            # capacity-profile namespace ("cpu", ...)

    def profile_variant(self, variant: str = "") -> str:
        """Namespace a capacity-profile variant by platform: caps
        learned on cpu-XLA must never warm a TPU run (and vice versa) —
        per-shard capacities, gamma and superstep budgets are all
        platform-shaped."""
        return f"{self.profile_ns}.{variant}" if variant \
            else self.profile_ns


def donation_default(platform: str) -> bool:
    """Donation policy: XLA:CPU ignores donation (with a warning), so
    it defaults on only for accelerator platforms; JAXMC_DONATE=1/0
    forces it either way (the ISSUE 6 rule, now a descriptor field)."""
    forced = os.environ.get("JAXMC_DONATE")
    if forced is not None:
        return forced == "1"
    return platform != "cpu"


def describe_backend(platform: Optional[str] = None,
                     device_count: Optional[int] = None
                     ) -> BackendDescriptor:
    """The descriptor for the LIVE jax backend (imports jax — call
    after device init).  `platform`/`device_count` override what jax
    reports (the mesh engines pass their actual mesh extent)."""
    import jax
    if platform is None:
        platform = jax.default_backend()
    if device_count is None:
        device_count = len(jax.devices())
    return BackendDescriptor(
        platform=platform, device_count=device_count,
        mesh_shape=(device_count,),
        donate=donation_default(platform),
        profile_ns=platform)
