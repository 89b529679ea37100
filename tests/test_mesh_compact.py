"""Contract of the mesh merge's two compactions (ISSUE 33).

After an a2a exchange the block a shard receives is D buckets of B rows
and D spill buckets of SB rows, each a valid prefix and padding (the
sender's `place()` cut them so).  `MeshExplorer._compact_runs_fn`
builds the `[VC]` block of valid candidates from 2*D slices; the
cumsum-rank row scatter it replaced on that path (three scatters of all
R received slots a level, 1.30 s of mesh-recheck-4p's 2.04 busy seconds
a search; ledger, PR 32) lives on here, in numpy, as the bit-for-bit
oracle — over blocks written by hand, and over blocks that the real
route delivers on 2 and 4 virtual devices (sparse, spilled, the
`mesh_skew` fault, POR-masked candidates).

`_merge_finish_fn` without a CONSTRAINT keeps a prefix, so it moves
nothing; with one (`specs/constoy`) it keeps the scatter form.

Structural guards: no scatter and no sort of the R received slots under
`jaxmc.compact` in the a2a superstep of a model without constraints;
the walker is shown to have teeth on the `gather` exchange, whose block
has no run structure and still scatters."""

import functools
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax import lax, shard_map  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from jaxmc.backend.bfs import SENTINEL  # noqa: E402
from jaxmc.front.cfg import parse_cfg  # noqa: E402
from jaxmc.sem.modules import Loader, bind_model  # noqa: E402

SPECS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "specs")

# the money-transfer race at toy size: an INVARIANT, no CONSTRAINT
_PLAIN_CFG = """SPECIFICATION Spec
INVARIANT AliceBounded
CONSTANTS
  Procs = {p1, p2}
  MaxMoney = 3
"""


def _model(name):
    if name in ("plain", "noinv"):
        spec, cfg = "transfer_scaled.tla", parse_cfg(
            _PLAIN_CFG if name == "plain" else
            _PLAIN_CFG.replace("INVARIANT AliceBounded\n", ""))
    else:
        spec = name + ".tla"
        with open(os.path.join(SPECS, name + ".cfg")) as fh:
            cfg = parse_cfg(fh.read())
    return bind_model(
        Loader([SPECS]).load_path(os.path.join(SPECS, spec)), cfg)


@functools.lru_cache(maxsize=None)
def _engine(D, name="plain", exchange="a2a"):
    from jaxmc.backend.mesh import MeshExplorer
    return MeshExplorer(_model(name), exchange=exchange,
                        mesh=Mesh(np.array(jax.devices()[:D]), ("d",)))


@pytest.fixture(autouse=True)
def _no_profile_store(tmp_path, monkeypatch):
    monkeypatch.setenv("JAXMC_PROFILE_STORE", str(tmp_path / "prof"))


# ------------------------------------------------ the oracle, in numpy

def _scatter_compact(gkeys, gcand, gsrc, VC):
    """The formulation up to PR 32: every valid row to the slot that is
    its rank among the valid rows (a cumsum), every other row dropped;
    the block starts in the empty form."""
    valid = gkeys[:, 0] == 0
    pos = np.cumsum(valid) - 1
    keep = valid & (pos < VC)
    ck = np.full((VC, gkeys.shape[1]), SENTINEL, np.int32)
    ck[:, 0] = 1
    ck[pos[keep]] = gkeys[keep]
    cc = np.full((VC, gcand.shape[1]), SENTINEL, np.int32)
    cc[pos[keep]] = gcand[keep]
    cs = np.zeros((VC,), np.int32)
    cs[pos[keep]] = gsrc[keep]
    return ck, cc, cs, int(valid.sum())


def _scatter_finish(new_rows, new_rows_u, new_src, explore):
    R = len(new_rows)
    pos = np.cumsum(explore) - 1
    fr = np.full(new_rows.shape, SENTINEL, np.int32)
    fu = np.full(new_rows_u.shape, SENTINEL, np.int32)
    fs = np.zeros((R,), np.int32)
    fr[pos[explore]] = new_rows[explore]
    fu[pos[explore]] = new_rows_u[explore]
    fs[pos[explore]] = new_src[explore]
    return fr, fu, fs, int(explore.sum())


# --------------------------------------- blocks written by hand

# scenario -> (VC as a share of R, valid rows per segment as a function
# of (D, B, SB, VC): D bucket counts then D spill counts)
def _one_segment(D, B, SB, VC):
    return [0] * (D - 1) + [min(B, VC) - 1] + [0] * D


def _spills(D, B, SB, VC):
    # peer 0's bucket full and its spill bucket in use, peer D-1's too
    c = [B] + [3] * (D - 2) + [B] + [SB // 2] + [0] * (D - 2) + [1]
    return c if D > 2 else [B, B, SB // 2, 1]


def _exactly_vc(D, B, SB, VC):
    c = [(VC - D * (SB // 2)) // D] * D + [SB // 2] * D
    c[0] += VC - sum(c)
    assert c[0] <= B
    return c


def _over_vc(D, B, SB, VC):
    c = _exactly_vc(D, B, SB, VC)
    c[-1] += 3
    return c


_BLOCKS = {
    "empty_level": (0.5, lambda D, B, SB, VC: [0] * (2 * D)),
    "one_segment_only": (0.5, _one_segment),
    "spill_segments_in_use": (0.9, _spills),
    # v_need = R > VC: overflow, with every slice at its full length
    "every_segment_full": (0.9, lambda D, B, SB, VC: [B] * D + [SB] * D),
    "v_need_exactly_vc": (0.5, _exactly_vc),
    "v_need_over_vc": (0.5, _over_vc),
    # VC under one bucket: the slices are capped at VC rows, and one
    # bucket alone overflows the block
    "vc_below_a_bucket": (0.1, lambda D, B, SB, VC:
                          [2] + [0] * (D - 2) + [VC + 2] + [0] * D),
    "last_segments_only": (0.5, lambda D, B, SB, VC:
                           [0] * (2 * D - 1) + [SB]),
}


def _block(counts, D, B, SB, K, PW, rng):
    """A received block as swap() hands it over: per segment `count`
    valid rows, then the wire's invalid row [1, SENTINEL...] (keys,
    packed row AND src)."""
    R = D * (B + SB)
    gkeys = np.full((R, K), SENTINEL, np.int32)
    gkeys[:, 0] = 1
    gcand = np.full((R, PW), SENTINEL, np.int32)
    gsrc = np.full((R,), SENTINEL, np.int32)
    starts = [d * B for d in range(D)] + \
        [D * B + d * SB for d in range(D)]
    for s, n in zip(starts, counts):
        gkeys[s:s + n] = rng.integers(-2 ** 31, 2 ** 31, (n, K))
        gkeys[s:s + n, 0] = 0
        gcand[s:s + n] = rng.integers(-2 ** 31, 2 ** 31, (n, PW))
        gsrc[s:s + n] = rng.integers(0, 2 ** 20, n)
    return gkeys, gcand, gsrc


@pytest.mark.parametrize("scenario", sorted(_BLOCKS))
@pytest.mark.parametrize("D", [2, 4])
def test_runs_equal_the_scatter_form(D, scenario):
    ex = _engine(D)
    B, SB = 40, 10
    R = D * (B + SB)
    share, counts_of = _BLOCKS[scenario]
    VC = int(share * R)
    counts = counts_of(D, B, SB, VC)
    assert len(counts) == 2 * D
    rng = np.random.default_rng([D, sorted(_BLOCKS).index(scenario)])
    gkeys, gcand, gsrc = _block(counts, D, B, SB, ex.K, ex.PW, rng)
    want = _scatter_compact(gkeys, gcand, gsrc, VC)
    got = jax.jit(ex._compact_runs_fn(B, SB, VC))(
        jnp.asarray(gkeys), jnp.asarray(gcand), jnp.asarray(gsrc))
    # v_need is the true count, over VC or not; the block is the first
    # VC valid rows even then (no start is clamped back into live rows)
    assert int(got[3]) == want[3] == sum(counts)
    for g, w in zip(got[:3], want[:3]):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert np.array_equal(np.asarray(g), w), scenario
    # the scenario is the one its name says
    over = {"every_segment_full", "v_need_over_vc", "vc_below_a_bucket"}
    assert (want[3] > VC) == (scenario in over)
    if scenario == "v_need_exactly_vc":
        assert want[3] == VC and (want[0][:, 0] == 0).all()
    if scenario == "empty_level":
        assert (want[0][:, 0] == 1).all() and not want[2].any()


# ------------------------------- blocks the real route delivers

# scenario -> (share of the C candidate slots that are valid, gamma,
# skew, POR-masked share of the valid ones)
_ROUTED = {
    "sparse": (0.05, 2.0, False, 0.0),
    "dense": (0.45, 2.0, False, 0.0),
    # buckets of C/D/2: most peers' runs run over into the spill bucket
    "spills": (0.55, 0.5, False, 0.0),
    # the mesh_skew fault: everything from every peer to shard 0
    "mesh_skew": (0.1, 2.0, True, 0.0),
    # device POR masks candidates in place before the route
    "por_masked": (0.4, 2.0, False, 0.5),
    "none_valid": (0.0, 2.0, False, 0.0),
}


@pytest.mark.parametrize("scenario", sorted(_ROUTED))
@pytest.mark.parametrize("D", [2, 4])
def test_runs_equal_the_scatter_form_on_routed_blocks(D, scenario,
                                                      monkeypatch):
    ex = _engine(D)
    share, gamma, skew, masked = _ROUTED[scenario]
    monkeypatch.setattr(ex, "_skew", skew)
    monkeypatch.setattr(ex, "_a2a_gamma", gamma)
    K, PW, C, FC = ex.K, ex.PW, 256, 16
    route, R, B, SB = ex._route_fn(C, FC)
    VC = R // 2
    compact = ex._compact_runs_fn(B, SB, VC)
    rng = np.random.default_rng([D, sorted(_ROUTED).index(scenario)])
    ckeys = rng.integers(-2 ** 31, 2 ** 31, (D, C, K)).astype(np.int32)
    cand = rng.integers(-2 ** 31, 2 ** 31, (D, C, PW)).astype(np.int32)
    cvalid = rng.random((D, C)) < share
    if masked:
        # what _mk_level_tail does to a POR-masked candidate
        keep = cvalid & (rng.random((D, C)) >= masked)
        ckeys[cvalid & ~keep, 1:] = SENTINEL
        cand[cvalid & ~keep] = SENTINEL
        cvalid = keep
    ckeys[..., 0] = np.where(cvalid, 0, 1)
    # the valid rows' owners balanced (to a row) on every sender, so
    # gamma alone decides what spills and nothing overflows
    ckeys[..., 1] = np.where(cvalid, np.cumsum(cvalid, axis=1), SENTINEL)

    def device(ckeys, cand, cvalid):
        g = route(ckeys[0], cand[0], cvalid[0], lax.axis_index("d"))
        gkeys, gcand, gsrc, a2a_ovf = g[0], g[1], g[2], g[4]
        out = (gkeys, gcand, gsrc, a2a_ovf) + compact(gkeys, gcand, gsrc)
        return tuple(o[None] for o in out)

    outs = jax.jit(shard_map(
        device, mesh=ex.mesh, in_specs=P("d"), out_specs=P("d"),
        check_vma=False))(ckeys, cand, cvalid)
    gkeys, gcand, gsrc, a2a_ovf, ck, cc, cs, need = map(np.asarray, outs)
    assert not a2a_ovf.any()
    assert int(need.sum()) == int(cvalid.sum())
    for d in range(D):
        want = _scatter_compact(gkeys[d], gcand[d], gsrc[d], VC)
        assert int(need[d]) == want[3]
        assert np.array_equal(ck[d], want[0]), (scenario, d)
        assert np.array_equal(cc[d], want[1]), (scenario, d)
        assert np.array_equal(cs[d], want[2]), (scenario, d)
    if scenario == "mesh_skew":
        assert need[0] == cvalid.sum() and not need[1:].any()
    if scenario == "spills":
        # a spill segment really held rows
        assert (gkeys[:, D * B:, 0] == 0).any()
    if scenario in ("sparse", "dense", "por_masked"):
        assert 0 < need.max() <= VC


# -------------------------------------------------- the merge's finish

@pytest.mark.parametrize("new_count", [0, 1, 37, 64])
@pytest.mark.parametrize("name", ["plain", "noinv"])
def test_finish_without_constraints_equals_the_scatter_form(name,
                                                            new_count):
    """`plain` has an INVARIANT (the unpacked rows are built and
    masked), `noinv` has none (front_rows_u IS front_rows)."""
    ex = _engine(2, name)
    assert not ex.constraint_fns and ex._finish_form == "prefix"
    assert bool(ex.inv_fns) == (name == "plain")
    N = 64
    rng = np.random.default_rng([new_count, name == "plain"])
    nvalid = np.arange(N) < new_count
    # as the merge hands them over: rows SENTINEL past new_count, src
    # whatever the take found there
    new_rows = np.where(
        nvalid[:, None],
        rng.integers(0, 2 ** 20, (N, ex.PW)), SENTINEL).astype(np.int32)
    new_src = rng.integers(0, 2 ** 20, N).astype(np.int32)
    new_rows_u = np.asarray(ex.plan.unpack_rows(jnp.asarray(new_rows))) \
        if ex.inv_fns else new_rows
    want = _scatter_finish(new_rows, new_rows_u, new_src, nvalid)
    got = jax.jit(ex._merge_finish_fn(N))(
        jnp.asarray(new_rows), jnp.asarray(new_src), jnp.asarray(nvalid))
    for g, w in zip(got[:3], want[:3]):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert np.array_equal(np.asarray(g), w)
    assert got[3].dtype == jnp.int32 and int(got[3]) == want[3]


def test_a_constraint_keeps_the_scatter_form_and_its_counts():
    from jaxmc import obs
    from jaxmc.backend.mesh import MeshExplorer
    from jaxmc.engine.explore import Explorer
    ri = Explorer(_model("constoy")).run()
    tel = obs.Telemetry()
    with obs.use(tel):
        me = MeshExplorer(_model("constoy"), exchange="a2a",
                          mesh=Mesh(np.array(jax.devices()[:2]), ("d",)))
        r = me.run()
    assert me.constraint_fns and me._finish_form == "scatter"
    assert (r.generated, r.distinct, r.ok) == \
        (ri.generated, ri.distinct, ri.ok)
    assert tel.gauges["mesh.finish_form"] == "scatter"
    assert tel.gauges["mesh.compact_form"] == "runs"
    # the kept rows are not a prefix there: the scatter form on rows
    # the constraint thins out
    N = 32
    rng = np.random.default_rng(33)
    new_rows_u = rng.integers(0, 12, (N, 2)).astype(np.int32)
    new_rows = np.asarray(me.plan.pack_rows(jnp.asarray(new_rows_u))[0])
    new_rows_u = np.asarray(me.plan.unpack_rows(jnp.asarray(new_rows)))
    new_src = rng.integers(0, 2 ** 20, N).astype(np.int32)
    nvalid = np.arange(N) < 29
    explore = nvalid.copy()
    for _, f in me.constraint_fns:
        explore &= np.asarray(jax.vmap(f)(jnp.asarray(new_rows_u)))
    assert 0 < explore.sum() < nvalid.sum()
    want = _scatter_finish(new_rows, new_rows_u, new_src, explore)
    got = jax.jit(me._merge_finish_fn(N))(
        jnp.asarray(new_rows), jnp.asarray(new_src), jnp.asarray(nvalid))
    for g, w in zip(got[:3], want[:3]):
        assert np.array_equal(np.asarray(g), w)
    assert int(got[3]) == want[3]


@pytest.mark.parametrize("D", [2, 4])
def test_the_forms_are_reported_and_the_counts_stand(D):
    """A whole search on the runs + prefix forms against the exact
    interpreter, and the two gauges that say which forms ran."""
    from jaxmc import obs
    from jaxmc.backend.mesh import MeshExplorer
    from jaxmc.engine.explore import Explorer
    ri = Explorer(_model("plain")).run()
    got = {}
    for exchange in ("a2a", "gather"):
        tel = obs.Telemetry()
        with obs.use(tel):
            r = MeshExplorer(
                _model("plain"), exchange=exchange,
                mesh=Mesh(np.array(jax.devices()[:D]), ("d",))).run()
        assert (r.generated, r.distinct, r.ok) == \
            (ri.generated, ri.distinct, ri.ok), exchange
        got[exchange] = (tel.gauges["mesh.compact_form"],
                         tel.gauges["mesh.finish_form"])
    assert got == {"a2a": ("runs", "prefix"),
                   "gather": ("scatter", "prefix")}


# ------------------------------------------------ structural guards

def _superstep_prims(ex, D):
    SC, FC, TRL, VC = 1 << 12, 64, 16, 128
    i32 = jnp.int32
    fn = ex._get_mesh_resident_step(SC, FC, TRL, VC).__wrapped__
    jaxpr = jax.make_jaxpr(fn)(
        jnp.zeros((D, SC, ex.K), i32), jnp.zeros((D,), i32),
        jnp.zeros((D, FC, ex.PW), i32), jnp.zeros((D,), i32),
        jnp.zeros((D, TRL, FC, ex.PW), i32), jnp.zeros((D, TRL, FC), i32),
        i32(0), i32(0), i32(0), i32(0))
    _, R, _, _ = ex._route_fn(ex.A * FC, FC)
    assert VC < R, "the program must hold a compaction"
    return _scoped_eqns(jaxpr.jaxpr), R


def _scoped_eqns(jaxpr, prefix=""):
    """(name stack, primitive, operand shapes) of every equation under
    a jaxpr.  An inner jaxpr's stacks are relative to the equation that
    holds it, so the walk carries the prefix down."""
    out = []
    for eqn in jaxpr.eqns:
        stack = prefix + "/" + str(eqn.source_info.name_stack)
        out.append((stack, eqn.primitive.name,
                    [tuple(getattr(v.aval, "shape", ()))
                     for v in eqn.invars]))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _scoped_eqns(sub, stack)
    return out


def test_the_a2a_superstep_compacts_without_a_scatter():
    """No scatter, row or scalar, and no sort over the R received slots
    under `jaxmc.compact` in the a2a superstep of a model without
    constraints: the valid candidates are 2*D slices, the kept rows a
    prefix."""
    ex = _engine(4)
    found, R = _superstep_prims(ex, 4)
    compact = [(p, shapes) for stack, p, shapes in found
               if "jaxmc.compact" in stack]
    prims = {p for p, _ in compact}
    # the scope is there, with the slices that build the block and the
    # takes of the new rows
    assert {"dynamic_update_slice", "gather"} <= prims, prims
    assert not [p for p in prims if p.startswith("scatter")], prims
    assert not [sh for p, sh in compact
                if p == "sort" and any(R in s for s in sh)]
    # 2*D segments; the keys as rows, each packed lane and src alone
    n_dus = sum(p == "dynamic_update_slice" for p, _ in compact)
    assert n_dus == 2 * 4 * (1 + ex.PW + 1), n_dus


def test_the_gather_exchange_still_scatters():
    """The walk has teeth: the gather exchange's block has no run
    structure, so its compaction keeps the three row scatters over all
    R = D*C replicated slots, under the same scope."""
    ex = _engine(4, exchange="gather")
    assert ex._compact_form == "scatter"
    found, R = _superstep_prims(ex, 4)
    scat = [sh for stack, p, sh in found
            if "jaxmc.compact" in stack and p == "scatter"
            and any(s[:1] == (R,) for s in sh)]
    assert len(scat) == 3, scat
