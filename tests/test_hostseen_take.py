"""The solo `host_seen` loop gathers a chunk's new rows on the device
(`bfs._take_rows_fast`) and an eager `jnp.take` is one XLA program per
index LENGTH: up to PR 38 a solo job compiled, or loaded from the
persistent cache, a program or two a chunk (292 and 530 for the two
primer jobs of the benchmark cell `ci-cohort-4p`, PR 39).  The index is
now padded to a power of two, so the programs are as many as the
buckets."""

import os

import numpy as np
import pytest

from jaxmc import obs
from jaxmc.backend import bfs
from jaxmc.session import CheckSession, SessionConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = os.path.join(REPO, "bench", "specs", "transfer_scaled.tla")
CFG = ("SPECIFICATION Spec\nINVARIANT AliceBounded\nCONSTANTS\n"
       "  Procs = {p1, p2, p3}\n  MaxMoney = 4\n")


@pytest.mark.parametrize("n", [1, 2, 255, 256, 257, 700, 1024])
def test_the_rows_are_the_hosts_fancy_index(n):
    import jax.numpy as jnp
    rng = np.random.default_rng(n)
    block = rng.integers(-5, 1 << 30, size=(1024, 3), dtype=np.int32)
    idx = np.sort(rng.choice(1024, size=n, replace=False))
    got = bfs._take_rows_fast(jnp.asarray(block), idx)
    assert isinstance(got, np.ndarray) and got.dtype == np.int32
    assert np.array_equal(got, block[idx])
    assert np.array_equal(bfs._take_rows_fast(block, idx), block[idx])


def test_a_gather_program_a_bucket_not_a_length():
    import jax.numpy as jnp

    from jaxmc.compile import cache
    cache._register_listeners()   # a session does; none was made yet
    block = jnp.asarray(np.arange(4096 * 2, dtype=np.int32).reshape(4096, 2))
    tel = obs.Telemetry(meta={})
    with obs.use_local(tel):
        for n in range(1, 1025, 7):   # 147 lengths, three buckets
            rows = bfs._take_rows_fast(block, np.arange(n))
            assert rows.shape == (n, 2) and rows[-1, 0] == 2 * (n - 1)
    compiles = tel.summary()["counters"]["compile.xla_compiles"]
    assert 1 <= compiles <= 6, compiles


def test_a_solo_host_seen_search_compiles_no_program_a_chunk(tmp_path):
    """93 chunks at `chunk` 64; the parent made 126 XLA programs here,
    one or two a chunk."""
    cfg_path = tmp_path / "t.cfg"
    cfg_path.write_text(CFG)
    tel = obs.Telemetry(meta={})
    with obs.use_local(tel):
        sess = CheckSession(
            SessionConfig(spec=SPEC, cfg=str(cfg_path), backend="jax",
                          host_seen=True, no_trace=True, chunk=64),
            tel=tel, log=obs.Logger(tel, quiet=True))
        sess.parse()
        sess.compile()
        res = sess.explore()
    assert (res.ok, res.generated, res.distinct) == (True, 11707, 5799)
    c = tel.summary()["counters"]
    assert c["compile.xla_compiles"] <= 20, c["compile.xla_compiles"]
    assert c["hostseen.chunks"] >= 90
