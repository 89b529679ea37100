"""Direct contract of the a2a route's sender side, `MeshExplorer._place_fn`
(ISSUE 31).

`place` builds the `[D, B, Pw]` bucket and `[D, SB, Pw]` spill-bucket
send buffers of a level by cutting each peer's contiguous run out of the
destination-sorted payload and masking past the run's end.  The
row-scatter formulation it replaced (two `[D*B+1, Pw]` / `[D*SB+1, Pw]`
tables, one scatter of all C payload rows into each, a scatter-add
histogram of the destinations; ~60 ns a row on the TPU v5e, 2.42 s of
mesh-recheck-4p's 4.65 busy seconds a search; ledger, PR 30) lives on
here alone, in numpy, as the bit-for-bit oracle.  `place` holds no
collective, so it runs here without a mesh axis.

Two structural guards: no scatter of any kind under `jaxmc.mesh.route`
in the resident superstep (the walker is shown to have teeth on the
merge's finish, which under this engine's CONSTRAINT still scatters the
kept rows under `jaxmc.compact`; since ISSUE 33 the valid-candidate
compaction ahead of it no longer does, tests/test_mesh_compact.py);
and the forced-spill run of tests/test_mesh_resident.py, which since
this issue also runs on four devices and holds its counts to the
one-chip engine's."""

import functools
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from jaxmc.backend.bfs import SENTINEL  # noqa: E402

SPECS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "specs")


def _constoy():
    from jaxmc.front.cfg import parse_cfg
    from jaxmc.sem.modules import Loader, bind_model
    with open(os.path.join(SPECS, "constoy.cfg")) as fh:
        cfg = parse_cfg(fh.read())
    return bind_model(
        Loader([SPECS]).load_path(os.path.join(SPECS, "constoy.tla")), cfg)


@functools.lru_cache(maxsize=None)
def _engine(D):
    """One a2a mesh engine per shard count, built once: `place` reads
    D, K, PW and the ownership formula from it and nothing else."""
    from jaxmc.backend.mesh import MeshExplorer
    return MeshExplorer(_constoy(), exchange="a2a",
                        mesh=Mesh(np.array(jax.devices()[:D]), ("d",)))


def _scatter_place(ckeys, cand, cvalid, me, D, B, SB, skew):
    """The formulation up to PR 30, in numpy: ranks inside each
    destination's run, a slot per payload row, one row scatter into the
    bucket table and one into the spill table (rows with no slot go to
    a dummy last row, cropped), a histogram of the destinations."""
    C, K = ckeys.shape
    Pw = K + cand.shape[1] + 1
    owner = np.zeros(C, np.int64) if skew else \
        (ckeys[:, 1].astype(np.uint32) % np.uint32(D)).astype(np.int64)
    dest = np.where(cvalid, owner, D)
    sperm = np.argsort(dest, kind="stable")
    sdest = dest[sperm]
    counts = np.bincount(dest, minlength=D + 1)
    excl = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(C) - excl[sdest]
    payload = np.concatenate(
        [ckeys[sperm], cand[sperm],
         (me * C + sperm).astype(np.int32)[:, None]], axis=1)
    slot1 = np.where((sdest < D) & (pos < B), sdest * B + pos, D * B)
    spos = pos - B
    slot2 = np.where((sdest < D) & (spos >= 0) & (spos < SB),
                     sdest * SB + spos, D * SB)
    b1 = np.full((D * B + 1, Pw), SENTINEL, np.int32)
    b1[:, 0] = 1
    b1[slot1] = payload
    b2 = np.full((D * SB + 1, Pw), SENTINEL, np.int32)
    b2[:, 0] = 1
    b2[slot2] = payload
    return (b1[:D * B].reshape(D, B, Pw), b2[:D * SB].reshape(D, SB, Pw),
            int(np.clip(counts[:D] - B, 0, SB).sum()),
            bool((counts[:D] > B + SB).any()), int(counts[:D].max()))


# scenario -> (C, B as a multiple of an even share C / D, share of the
# candidates that are valid, skew); SB = max(1, B // 4) as the engine
# sizes it
_SCENARIOS = {
    # a level of the cell: ~5 % of the slots hold a candidate
    "sparse": (512, 2.0, 0.05, False),
    # every slot valid, the buckets hold them: C/D a peer under B
    "full": (512, 1.25, 1.0, False),
    # C/D a peer over B, inside B + SB
    "spills": (512, 0.875, 1.0, False),
    # past bucket AND spill: a2a_ovf, rows beyond B + SB dropped
    "overflows": (512, 0.5, 1.0, False),
    # the mesh_skew fault: every row to shard 0, the other runs empty
    "skew": (512, 2.0, 0.6 / 4, True),
    "skew_overflows": (512, 1.0, 0.9, True),
    "none_valid": (512, 2.0, 0.0, False),
    # the ceil(FC / D) floor of _a2a_bucket: a bucket longer than the
    # candidate block, so every slice runs into the padding
    "c_below_b": (40, 1.6 * 4, 0.7, False),
    "one_row_buckets": (24, 0.0, 0.5, False),
}


def _level(scenario, D, K, PW, rng):
    C, factor, share, skew = _SCENARIOS[scenario]
    B = max(1, int(factor * C / D))
    ckeys = rng.integers(-2 ** 31, 2 ** 31, (C, K)).astype(np.int32)
    cand = rng.integers(-2 ** 31, 2 ** 31, (C, PW)).astype(np.int32)
    cvalid = rng.random(C) < share
    if share == 1.0:
        # every slot valid and the owners exactly balanced, in a
        # shuffled order: C / D rows a peer, so the bucket factor alone
        # decides what spills
        cvalid[:] = True
        ckeys[:, 1] = rng.permutation(C).astype(np.int32)
    # the validity lane as the engine writes it; invalid candidates
    # keep arbitrary data words (place must mask them by run, not by
    # looking at them)
    ckeys[:, 0] = np.where(cvalid, 0, 1)
    return C, B, max(1, B // 4), skew, ckeys, cand, cvalid


@pytest.mark.parametrize("scenario", sorted(_SCENARIOS))
@pytest.mark.parametrize("D", [2, 4])
def test_place_equals_the_scatter_form(D, scenario, monkeypatch):
    ex = _engine(D)
    rng = np.random.default_rng(
        [D, sorted(_SCENARIOS).index(scenario)])
    C, B, SB, skew, ckeys, cand, cvalid = _level(
        scenario, D, ex.K, ex.PW, rng)
    monkeypatch.setattr(ex, "_skew", skew)
    place = jax.jit(ex._place_fn(C, B, SB))
    for me in (0, D - 1):
        want = _scatter_place(ckeys, cand, cvalid, me, D, B, SB, skew)
        got = place(jnp.asarray(ckeys), jnp.asarray(cand),
                    jnp.asarray(cvalid), jnp.int32(me))
        b1, b2, spill_local, a2a_ovf, maxdest_local = got
        assert b1.shape == want[0].shape and b2.shape == want[1].shape
        assert np.array_equal(np.asarray(b1), want[0]), scenario
        assert np.array_equal(np.asarray(b2), want[1]), scenario
        assert (int(spill_local), bool(a2a_ovf), int(maxdest_local)) \
            == want[2:], scenario
    # the scenario is the one its name says
    n_sent = int((want[0][..., 0] == 0).sum()
                 + (want[1][..., 0] == 0).sum())
    if scenario == "spills":
        assert want[2] > 0 and not want[3]
    if scenario in ("overflows", "skew_overflows"):
        assert want[3] and n_sent < int(cvalid.sum())
    if scenario in ("sparse", "full", "skew", "c_below_b"):
        assert want[2] == 0 and n_sent == int(cvalid.sum())
    if scenario.startswith("skew"):
        assert (want[0][1:, :, 0] == 1).all()
    if scenario == "none_valid":
        assert n_sent == 0 and want[4] == 0


# ------------------------------------------------ structural guards

def _scoped_prims(jaxpr, prefix=""):
    """(name stack, primitive) of every equation under a jaxpr.  An
    inner jaxpr's stacks are relative to the equation that holds it, so
    the walk carries the prefix down."""
    out = []
    for eqn in jaxpr.eqns:
        stack = prefix + "/" + str(eqn.source_info.name_stack)
        out.append((stack, eqn.primitive.name))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _scoped_prims(sub, stack)
    return out


def test_the_superstep_routes_without_a_scatter():
    """No scatter, row or scalar, and no scatter-add under
    `jaxmc.mesh.route` in the resident superstep: the buckets are
    slices of the sorted payload, the run borders compare-and-sum
    reductions."""
    ex = _engine(4)
    D, SC, FC, TRL, VC = 4, 1 << 12, 64, 16, 128
    i32 = jnp.int32
    fn = ex._get_mesh_resident_step(SC, FC, TRL, VC).__wrapped__
    jaxpr = jax.make_jaxpr(fn)(
        jnp.zeros((D, SC, ex.K), i32), jnp.zeros((D,), i32),
        jnp.zeros((D, FC, ex.PW), i32), jnp.zeros((D,), i32),
        jnp.zeros((D, TRL, FC, ex.PW), i32), jnp.zeros((D, TRL, FC), i32),
        i32(0), i32(0), i32(0), i32(0))
    found = _scoped_prims(jaxpr.jaxpr)
    route = [p for stack, p in found if "jaxmc.mesh.route" in stack]
    # the scope is there, with the sort, the gathers and the slices
    assert {"sort", "gather", "dynamic_slice"} <= set(route), set(route)
    assert not [p for p in route if p.startswith("scatter")], route
    # the walk has teeth: constoy's cfg has a CONSTRAINT, so the merge's
    # finish still compacts the kept rows by scatter, under its own scope
    assert "scatter" in {p for stack, p in found if "jaxmc.compact" in stack}
