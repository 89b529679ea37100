r"""Warm-start steady-state bench machinery (ISSUE 5).

The contract: a resident-mode truncation checkpoint is RESUMABLE, and a
resumed run's final counts are bit-identical to a cold run's — so the
bench's steady-state window (timed run resumed from the warm
checkpoint) measures exactly the cold workload with compile/warm-up
excluded.  Repo-local models only (transfer_scaled, symtoy); the bench
model itself needs the reference tree and is covered by the slow-marked
leg at the bottom.
"""

import os

import pytest

import jax

jax.config.update("jax_platforms", "cpu")

from jaxmc.front.cfg import parse_cfg  # noqa: E402
from jaxmc.sem.modules import Loader, bind_model  # noqa: E402
from jaxmc.backend.bfs import TpuExplorer  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = os.path.join(REPO, "specs")


def load(spec, cfg):
    ldr = Loader([SPECS, "/root/reference/examples"])
    with open(os.path.join(SPECS, cfg)) as fh:
        return bind_model(ldr.load_path(os.path.join(SPECS, spec)),
                          parse_cfg(fh.read()))


def test_resident_truncation_checkpoint_resume_parity(tmp_path):
    # cold truncated run vs (warm prefix -> checkpoint -> resume) at the
    # same bound: counts, diameter and truncation must be identical.
    # max_states is evaluated per LEVEL inside the device loop, so the
    # truncation point is deterministic regardless of dispatch batching.
    cold = TpuExplorer(load("transfer_scaled.tla",
                            "transfer_scaled.cfg"),
                       store_trace=False, resident=True,
                       max_states=8000).run()
    assert cold.truncated
    ck = str(tmp_path / "warm.ck")
    rw = TpuExplorer(load("transfer_scaled.tla", "transfer_scaled.cfg"),
                     store_trace=False, resident=True, max_states=600,
                     checkpoint_path=ck).run()
    assert rw.truncated and os.path.exists(ck), \
        "truncation must write a resumable checkpoint"
    assert rw.distinct < cold.distinct, "prefix must stop earlier"
    r = TpuExplorer(load("transfer_scaled.tla", "transfer_scaled.cfg"),
                    store_trace=False, resident=True, max_states=8000,
                    resume_from=ck).run()
    assert (r.generated, r.distinct, r.diameter, r.truncated) == \
        (cold.generated, cold.distinct, cold.diameter, cold.truncated)


def test_resident_warm_resume_full_run_parity(tmp_path):
    # the bench shape end to end on a tiny model: cold COMPLETE run vs
    # warm-checkpoint resume run to completion — bit-identical totals
    # and verdict
    cold = TpuExplorer(load("symtoy.tla", "symtoy.cfg"),
                       store_trace=False, resident=True).run()
    ck = str(tmp_path / "warm.ck")
    TpuExplorer(load("symtoy.tla", "symtoy.cfg"), store_trace=False,
                resident=True, max_states=8, checkpoint_path=ck).run()
    r = TpuExplorer(load("symtoy.tla", "symtoy.cfg"), store_trace=False,
                    resident=True, resume_from=ck).run()
    assert (r.generated, r.distinct, r.ok, r.truncated) == \
        (cold.generated, cold.distinct, cold.ok, cold.truncated)


def test_res_caps_hint_respected():
    # the bench passes known steady-state caps so the one warm-up
    # compile covers the whole run — the hint must floor the defaults
    ex = TpuExplorer(load("symtoy.tla", "symtoy.cfg"),
                     store_trace=False, resident=True,
                     res_caps={"SC": 1 << 16})
    ex.run()
    assert ex._res_caps["SC"] >= (1 << 16)


@pytest.mark.slow
def test_bench_model_warm_resume_parity(tmp_path):
    # the ISSUE 5 acceptance pin on the REAL bench model (needs the
    # reference raft tree; slow): warm resume reproduces the manifest's
    # cold-run totals exactly
    from jaxmc.corpus import case_for_cfg
    pin = case_for_cfg("MCraft_3s_bench.cfg")
    assert pin is not None and pin.distinct is not None
    ck = str(tmp_path / "warm.ck")
    TpuExplorer(load("MCraftMicro.tla", "MCraft_3s_bench.cfg"),
                store_trace=False, resident=True, max_states=20000,
                checkpoint_path=ck).run()
    r = TpuExplorer(load("MCraftMicro.tla", "MCraft_3s_bench.cfg"),
                    store_trace=False, resident=True,
                    resume_from=ck).run()
    assert (r.distinct, r.generated) == (pin.distinct, pin.generated)
    assert r.ok and not r.truncated
