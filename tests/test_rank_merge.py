"""Direct contract of backend/bfs._rank_merge (ISSUE 25).

The function places rows by gather through inverse indices built from
scalar scatters.  The row-scatter formulation it replaced lives on here
alone, as the bit-for-bit oracle, beside a numpy sorted-set-union
reference that shares no code with either; a lowering guard keeps row
scatters (39-117 ns a row on the TPU v5e against 4.3 ns for a gathered
row; ledger, PR 24) from coming back.

Since ISSUE 27 the probe inside it (`_seen_probe`, `_lower_bound`)
searches the valid prefix of the sorted keys alone, a block of QB
queries at a time, for bit_length(seen_count) rounds: the oracle calls
`_seen_probe` without a live count (every block), so the cases here
also hold the prefix form against the general one; a second lowering
guard keeps whole-capacity gathers out of the probe.

Since ISSUE 28 `_rank_merge` is the engines' ONLY dedup merge; a third
lowering guard keeps a sort over seen + candidates out of the level
step and the mesh superstep."""

import functools
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from jaxmc.backend import bfs  # noqa: E402
from jaxmc.backend.bfs import (  # noqa: E402
    SENTINEL, _lsd_sort, _probe_block_rows, _probe_blocks, _rank_merge,
    _seen_probe)


def _scatter_rank_merge(seen, seen_count, keys, N, SC, K, multikey=False):
    """The formulation up to PR 24: histogram ranks and three row
    scatters.  Kept verbatim as the oracle."""
    sidx = jnp.arange(N, dtype=jnp.int32)
    if multikey:
        res = lax.sort(tuple(keys[:, j] for j in range(K)) + (sidx,),
                       num_keys=K, is_stable=True)
        kc = list(res[:K])
        sidx_s = res[K]
    else:
        kc, ec = _lsd_sort([keys[:, j] for j in range(K)], [sidx])
        sidx_s = ec[0]
    skeys = jnp.stack(kc, axis=1)
    svalid = skeys[:, 0] == 0
    neq_prev = jnp.concatenate([
        jnp.array([True]),
        jnp.any(skeys[1:] != skeys[:-1], axis=1)])
    words = skeys[:, 1:]
    found, lb = _seen_probe(seen, seen_count, skeys, SC)
    new = svalid & ~found & neq_prev
    new_count = jnp.sum(new, dtype=jnp.int32)
    npos = jnp.cumsum(new.astype(jnp.int32)) - 1
    tgt = jnp.where(new, npos, N + sidx)
    nk_words = jnp.zeros((N, K - 1), jnp.int32) \
        .at[tgt].set(words, mode="drop", unique_indices=True)
    nk_sidx = jnp.zeros((N,), jnp.int32) \
        .at[tgt].set(sidx_s, mode="drop", unique_indices=True)
    nk_lb = jnp.zeros((N,), jnp.int32) \
        .at[tgt].set(lb, mode="drop", unique_indices=True)
    nvalid = sidx < new_count
    hist = jnp.zeros((SC + 1,), jnp.int32)
    hist = hist.at[jnp.where(nvalid, jnp.clip(nk_lb, 0, SC), SC)] \
        .add(1)
    ranks = jnp.cumsum(hist[:SC])
    valid_seen_rows = jnp.arange(SC) < seen_count
    pos_s = jnp.where(valid_seen_rows,
                      jnp.arange(SC, dtype=jnp.int32) + ranks,
                      SC + jnp.arange(SC, dtype=jnp.int32))
    seen2 = jnp.full((SC, K), SENTINEL, jnp.int32)
    seen2 = seen2.at[:, 0].set(1)
    seen2 = seen2.at[pos_s].set(seen, mode="drop",
                                unique_indices=True)
    nk_full = jnp.concatenate(
        [jnp.zeros((N, 1), jnp.int32), nk_words], axis=1)
    pos_n = jnp.where(nvalid, nk_lb + sidx, SC + sidx)
    seen2 = seen2.at[pos_n].set(nk_full, mode="drop",
                                unique_indices=True)
    return dict(new_count=new_count, nk_sidx=nk_sidx, seen2=seen2,
                seen_count2=seen_count + new_count)


# what both references answer (the oracle predates `probe_blocks`)
ANSWER = ("new_count", "nk_sidx", "seen2", "seen_count2")


def _lexsorted(words):
    """Distinct rows of `words` in signed lexicographic order."""
    return np.unique(words, axis=0)


def _table(words, SC, K):
    """A seen table: `words` as the valid prefix, the invalid tail."""
    t = np.full((SC, K), SENTINEL, np.int32)
    t[:, 0] = 1
    t[:len(words), 0] = 0
    t[:len(words), 1:] = words
    return t


def _keys(words, valid, K):
    k = np.full((len(valid), K), SENTINEL, np.int32)
    k[:, 0] = 1
    k[valid, 0] = 0
    k[valid, 1:] = words[valid]
    return k


def _np_reference(seen, seen_count, keys, SC, K):
    """Sorted set union in plain numpy."""
    old = [tuple(r) for r in seen[:seen_count, 1:]]
    have = set(old)
    first = {}
    for i, r in enumerate(keys):
        if r[0] == 0:
            first.setdefault(tuple(r[1:]), i)
    fresh = sorted(w for w in first if w not in have)
    merged = sorted(old + fresh)
    seen2 = _table(np.array(merged[:SC], np.int32).reshape(-1, K - 1),
                   SC, K)
    nk_sidx = np.zeros(len(keys), np.int32)
    nk_sidx[:len(fresh)] = [first[w] for w in fresh]
    return dict(new_count=len(fresh), nk_sidx=nk_sidx, seen2=seen2,
                seen_count2=seen_count + len(fresh))


N, SC = 48, 64
# query blocks of the probe at these toy shapes: 16 rows, so the 48 keys
# are three blocks and a level's valid keys end inside any of them.
# Within a block every 4th sorted key (and the last) is searched in
# full first: 5 samples, 4 groups.  Every trace of this file runs under
# the same values (jit caches by function, and they are read when a
# shape is first traced)
PROBE_MIN, PROBE_SAMPLE = 16, 4


@pytest.fixture(autouse=True)
def _toy_probe_shape(monkeypatch):
    monkeypatch.setattr(bfs, "_PROBE_BLOCK_MIN", PROBE_MIN)
    monkeypatch.setattr(bfs, "_PROBE_SAMPLE", PROBE_SAMPLE)


SCENARIOS = ("empty_seen", "full_seen", "no_valid_keys", "all_seen",
             "all_equal", "random", "overflow")


def _case(scenario, K, rng):
    """(seen table, seen_count, keys) for one scenario.  Words come
    from a small alphabet (negatives included: the order is signed) so
    that random cases hold duplicates and keys already seen."""
    def draw(n):
        return rng.integers(-3, 4, size=(n, K - 1)).astype(np.int32)

    pool = _lexsorted(draw(4 * SC))
    valid = rng.random(N) < 0.8
    kwords = draw(N)
    n_seen = min(SC // 2, len(pool))
    if scenario == "empty_seen":
        n_seen = 0
    elif scenario == "full_seen":
        n_seen = min(SC, len(pool))
    elif scenario == "no_valid_keys":
        valid[:] = False
    elif scenario == "all_equal":
        kwords[:] = kwords[0]
        valid[:] = True
    elif scenario == "overflow":
        # SC - 8 seen rows and more than 8 new keys, spread over the
        # whole key range so that seen rows are pushed past SC too
        wide = _lexsorted(
            rng.integers(-99, 100, size=(SC + N, K - 1)).astype(np.int32))
        pick = rng.permutation(len(wide))
        n_seen = SC - 8
        kwords = wide[pick[n_seen:n_seen + N]]
        valid[:] = True
        pool = _lexsorted(wide[pick[:n_seen]])
    swords = pool[np.sort(rng.choice(len(pool), n_seen, replace=False))]
    if scenario == "all_seen" and n_seen:
        kwords = swords[rng.integers(0, n_seen, N)]
    return _table(swords, SC, K), n_seen, _keys(kwords, valid, K)


# rows of seen2 built at a time: the whole table (the engines' case up
# to SC 2^20), four even blocks, three blocks that overhang SC
BLOCKS = {"one_block": bfs._MERGE_BLOCK_ROWS, "even_blocks": 16,
          "uneven_blocks": 24}
# a partial per block size: jit's cache is keyed on the function, and
# the block size is read when a (K, multikey) is first traced — always
# under the monkeypatch of the same value.  Shared by the scenarios,
# whose shapes are the same, so each compiles once
_FNS = {name: jax.jit(functools.partial(_rank_merge),
                      static_argnums=(3, 4, 5, 6)) for name in BLOCKS}
_ORACLE = jax.jit(_scatter_rank_merge, static_argnums=(3, 4, 5, 6))


@pytest.mark.parametrize("blocks", BLOCKS)
@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("multikey", [False, True])
@pytest.mark.parametrize("K", [3, 5])
def test_rank_merge_equals_oracle_and_set_union(K, multikey, scenario,
                                                blocks, monkeypatch):
    monkeypatch.setattr(bfs, "_MERGE_BLOCK_ROWS", BLOCKS[blocks])
    fn, oracle = _FNS[blocks], _ORACLE
    for trial in range(6):
        rng = np.random.default_rng(
            [K, int(multikey), SCENARIOS.index(scenario), trial])
        seen, n_seen, keys = _case(scenario, K, rng)
        got = fn(jnp.asarray(seen), jnp.int32(n_seen), jnp.asarray(keys),
                 N, SC, K, multikey)
        want = oracle(jnp.asarray(seen), jnp.int32(n_seen),
                      jnp.asarray(keys), N, SC, K, multikey)
        ref = _np_reference(seen, n_seen, keys, SC, K)
        n_valid = int((keys[:, 0] == 0).sum())
        assert int(got["probe_blocks"]) == -(-n_valid // PROBE_MIN)
        for name in ANSWER:
            g = np.asarray(got[name])
            assert g.dtype == np.asarray(want[name]).dtype, name
            assert np.array_equal(g, np.asarray(want[name])), \
                (name, "oracle", trial)
            assert np.array_equal(g, ref[name]), (name, "numpy", trial)
        # the invariant the next level's binary searches rest on
        s2 = np.asarray(got["seen2"])
        n2 = min(int(got["seen_count2"]), SC)
        assert np.all(s2[:n2, 0] == 0)
        assert np.all(s2[n2:, 0] == 1)
        assert np.all(s2[n2:, 1:] == SENTINEL)
        pre = [tuple(r) for r in s2[:n2, 1:]]
        assert all(a < b for a, b in zip(pre, pre[1:]))
        if scenario == "overflow":
            assert int(got["seen_count2"]) > SC
        if scenario in ("no_valid_keys", "all_seen"):
            assert int(got["new_count"]) == 0
            assert np.array_equal(s2, seen)
        if scenario == "all_equal":
            assert int(got["new_count"]) <= 1


# ---- the probe sized by what is live (ISSUE 27) ----
#
# (N, K, multikey): the resident engine's shape cut to 48 keys (QB 16,
# three even blocks) and the level engine's A x FC = 163,840 cut by
# 4,096 to 40 (QB 16: blocks at 0, 16 and — clamped — 24, so the last
# overlaps its neighbour)
PROBE_SHAPES = {"even": (48, 5, False), "uneven": (40, 3, True)}
QB = 16
N_LIVE = {"0": 0, "1": 1, "QB-1": QB - 1, "QB": QB, "QB+1": QB + 1,
          "N": None}
SEEN_COUNT = {"0": 0, "1": 1, "pow2": 16, "SC": SC}


def _probe_case(n, K, n_live, n_seen, rng):
    """n_live valid keys (at random slots: the merge sorts them to the
    front) over n slots, n_seen seen rows; the small alphabet makes
    duplicates and keys already seen."""
    def draw(m):
        return rng.integers(-4, 5, size=(m, K - 1)).astype(np.int32)

    pool = _lexsorted(draw(16 * SC))
    swords = pool[np.sort(rng.choice(len(pool), n_seen, replace=False))]
    valid = np.zeros(n, bool)
    valid[rng.choice(n, n_live, replace=False)] = True
    return _table(swords, SC, K), _keys(draw(n), valid, K)


@pytest.mark.parametrize("seen_count", SEEN_COUNT)
@pytest.mark.parametrize("n_live", N_LIVE)
@pytest.mark.parametrize("shape", PROBE_SHAPES)
def test_probe_follows_live_queries_and_seen_count(shape, n_live,
                                                   seen_count):
    n, K, multikey = PROBE_SHAPES[shape]
    assert _probe_block_rows(n) == QB
    live = n if N_LIVE[n_live] is None else N_LIVE[n_live]
    n_seen = SEEN_COUNT[seen_count]
    for trial in range(3):
        rng = np.random.default_rng([n, live, n_seen, trial])
        seen, keys = _probe_case(n, K, live, n_seen, rng)
        args = (jnp.asarray(seen), jnp.int32(n_seen), jnp.asarray(keys),
                n, SC, K, multikey)
        # the merge's own block size, as the engines run it
        got = _FNS["one_block"](*args)
        want = _ORACLE(*args)
        ref = _np_reference(seen, n_seen, keys, SC, K)
        # the blocks that hold a live key, and no other
        assert int(got["probe_blocks"]) == -(-live // QB) \
            == _probe_blocks(live, n)
        for name in ANSWER:
            g = np.asarray(got[name])
            assert np.array_equal(g, np.asarray(want[name])), \
                (name, "oracle", trial)
            assert np.array_equal(g, ref[name]), (name, "numpy", trial)


def _np_probe(seen, n_seen, keys):
    """(found, lower bound) of every key row, by bisection over tuples."""
    import bisect
    table = [tuple(r) for r in seen[:n_seen, 1:]]
    lb = np.array([bisect.bisect_left(table, tuple(r)) for r in keys[:, 1:]])
    found = np.array([i < n_seen and table[i] == tuple(r)
                      for i, r in zip(lb, keys[:, 1:])], bool)
    return found, lb


@pytest.mark.parametrize("K", [3, 5])
def test_seen_probe_of_unsorted_keys_searches_every_block(K):
    """The POR filter's form: no live count, keys in candidate order,
    invalid rows anywhere."""
    probe = jax.jit(_seen_probe, static_argnums=(3,))
    for trial in range(4):
        rng = np.random.default_rng([K, trial, 27])
        n_seen = int(rng.integers(0, SC + 1))
        seen, keys = _probe_case(N, K, int(rng.integers(0, N + 1)), n_seen,
                                 rng)
        found, lb = probe(jnp.asarray(seen), jnp.int32(n_seen),
                          jnp.asarray(keys), SC)
        valid = keys[:, 0] == 0
        want_found, want_lb = _np_probe(seen, n_seen, keys)
        assert np.array_equal(np.asarray(found)[valid], want_found[valid])
        assert np.array_equal(np.asarray(lb)[valid], want_lb[valid])
        # SENTINEL words sort past every row of the prefix
        assert not np.asarray(found)[~valid].any()
        assert np.all((0 <= np.asarray(lb)) & (np.asarray(lb) <= n_seen))


def test_seen_probe_leaves_blocks_past_the_live_prefix_alone():
    """Every key IS in the table; with a live count the blocks past it
    are never searched and say so: found False, lb 0."""
    K = 5
    rng = np.random.default_rng(27)
    words = _lexsorted(rng.integers(-9, 10, size=(4 * SC, K - 1))
                       .astype(np.int32))[:SC]
    seen = _table(words, SC, K)
    keys = _keys(words[np.sort(rng.choice(SC, N, replace=False))],
                 np.ones(N, bool), K)
    probe = jax.jit(_seen_probe, static_argnums=(3,))
    for live in (0, 1, QB, QB + 1, N, N + 7):
        found, lb = probe(jnp.asarray(seen), jnp.int32(SC),
                          jnp.asarray(keys), SC, jnp.int32(live))
        ran = min(-(-live // QB) * QB, N)
        assert np.asarray(found)[:ran].all()
        assert np.array_equal(np.asarray(lb)[:ran],
                              _np_probe(seen, SC, keys)[1][:ran])
        assert not np.asarray(found)[ran:].any()
        assert not np.asarray(lb)[ran:].any()


@pytest.mark.parametrize("K", [3, 5])
def test_sorted_probe_trusts_only_the_live_prefix_to_ascend(K):
    """sorted_keys: the first n_live rows ascend (with duplicates, seen
    and unseen); what follows them is garbage in no order, as nothing
    promises otherwise.  The live rows' answers are the bisection's."""
    probe = jax.jit(_seen_probe, static_argnums=(3, 5))
    for trial in range(8):
        rng = np.random.default_rng([K, trial, 270])
        n_seen = int(rng.integers(0, SC + 1))
        live = int(rng.integers(0, N + 1))
        seen, keys = _probe_case(N, K, N, n_seen, rng)
        order = np.lexsort(tuple(keys[:live, j]
                                 for j in reversed(range(1, K))))
        keys[:live] = keys[:live][order]
        found, lb = probe(jnp.asarray(seen), jnp.int32(n_seen),
                          jnp.asarray(keys), SC, jnp.int32(live), True)
        want_found, want_lb = _np_probe(seen, n_seen, keys)
        assert np.array_equal(np.asarray(found)[:live], want_found[:live])
        assert np.array_equal(np.asarray(lb)[:live], want_lb[:live])


def _gathers(jaxpr, loops=0):
    """(enclosing while loops, operand shape, result shape) of every
    gather under a jaxpr."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            out.append((loops, eqn.invars[0].aval.shape,
                        eqn.outvars[0].aval.shape))
        inner = loops + (eqn.primitive.name == "while")
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _gathers(sub, inner)
    return out


_GATHER = re.compile(
    r'"?stablehlo\.gather"?\(.*? : \(tensor<([^>]*)>, tensor<([^>]*)>\)'
    r' -> tensor<([^>]*)>')


@pytest.mark.parametrize("multikey", [False, True])
@pytest.mark.parametrize("K", [3, 5])
def test_rank_merge_probes_by_block_inside_loops(K, multikey):
    shapes = (jax.ShapeDtypeStruct((SC, K), jnp.int32),
              jax.ShapeDtypeStruct((), jnp.int32),
              jax.ShapeDtypeStruct((N, K), jnp.int32))
    text = jax.jit(_rank_merge, static_argnums=(3, 4, 5, 6)).lower(
        *shapes, N, SC, K, multikey).as_text()
    found = _GATHER.findall(text)
    assert len(found) == len(re.findall(r'stablehlo\.gather"?\(', text))
    # the probe gathers from the table's K-1 data words; the merge's
    # tail from whole K-word rows
    probe = [g for g in found if g[0] == f"{SC}x{K - 1}xi32"]
    samples = QB // PROBE_SAMPLE + 1
    # QB rows a gather, or the block's samples: never the N query slots
    # (jit lowers equal takes to one shared function)
    assert {int(r.split("x")[0]) for _, _, r in probe} == {samples, QB}, \
        found
    jaxpr = jax.make_jaxpr(
        lambda s, c, k: _rank_merge(s, c, k, N, SC, K, multikey))(*shapes)
    depth = sorted(loops for loops, operand, result in _gathers(jaxpr.jaxpr)
                   if operand == (SC, K - 1))
    # the rows found, once a block; the sampled and the bounded search,
    # once a round each
    assert depth == [1, 2, 2], depth
    for loops, operand, result in _gathers(jaxpr.jaxpr):
        assert result[0] != N or operand[1] == K, (operand, result)


_SCATTER = re.compile(
    r'"?stablehlo\.scatter"?\(.*?\}\) : \(([^)]*)\) -> ', re.S)


def _scatters(fn, K, multikey):
    """(operand, indices, updates) tensor types of every scatter in the
    function's lowered text."""
    text = jax.jit(fn, static_argnums=(3, 4, 5, 6)).lower(
        jax.ShapeDtypeStruct((SC, K), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((N, K), jnp.int32),
        N, SC, K, multikey).as_text()
    # every scatter op (its attribute #stablehlo.scatter<...> aside)
    # must have been parsed
    assert len(re.findall(r'stablehlo\.scatter"?\(', text)) \
        == len(_SCATTER.findall(text))
    return [tuple(re.findall(r"tensor<([^>]*)>", m))
            for m in _SCATTER.findall(text)]


@pytest.mark.parametrize("multikey", [False, True])
@pytest.mark.parametrize("K", [3, 5])
def test_rank_merge_lowers_to_scalar_scatters_only(K, multikey):
    found = _scatters(_rank_merge, K, multikey)
    assert 1 <= len(found) <= 2, found
    for operand, indices, updates in found:
        # N indices, one i32 each: no [SC, K] or [N, K-1] row updates
        assert indices == f"{N}x1xi32", found
        assert updates == f"{N}xi32", found
        assert operand in (f"{N}xi32", f"{SC}xi32"), found
    # the guard has teeth: the formulation it replaced fails it
    old = _scatters(_scatter_rank_merge, K, multikey)
    assert any("x" in u.split("xi32")[0] for _, _, u in old), old


# ------------------------------------------- the engines' one dedup merge

_SORT = re.compile(
    r'"?stablehlo\.sort"?\(.*?\}\) : \(([^)]*)\) -> ', re.S)


def _constoy():
    import os
    from jaxmc.front.cfg import parse_cfg
    from jaxmc.sem.modules import Loader, bind_model
    specs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "specs")
    with open(os.path.join(specs, "constoy.cfg")) as fh:
        cfg = parse_cfg(fh.read())
    return bind_model(
        Loader([specs]).load_path(os.path.join(specs, "constoy.tla")), cfg)


def _lowered_level_step(SC, FC):
    from jaxmc.backend.bfs import TpuExplorer
    ex = TpuExplorer(_constoy())
    i32 = jnp.int32
    text = ex._get_step(SC, FC).__wrapped__.lower(
        jnp.zeros((SC, ex.K), i32), i32(0),
        jnp.zeros((FC, ex.PW), i32), i32(0)).as_text()
    return text, ex.A * FC


def _lowered_mesh_superstep(SC, FC):
    from jax.sharding import Mesh
    from jaxmc.backend.mesh import MeshExplorer
    D, TRL, VC = 4, 16, 2 * FC
    ex = MeshExplorer(_constoy(), exchange="a2a",
                      mesh=Mesh(np.array(jax.devices()[:D]), ("d",)))
    i32 = jnp.int32
    text = ex._get_mesh_resident_step(SC, FC, TRL, VC).__wrapped__.lower(
        jnp.zeros((D, SC, ex.K), i32), jnp.zeros((D,), i32),
        jnp.zeros((D, FC, ex.PW), i32), jnp.zeros((D,), i32),
        jnp.zeros((D, TRL, FC, ex.PW), i32), jnp.zeros((D, TRL, FC), i32),
        i32(0), i32(0), i32(0), i32(0)).as_text()
    return text, ex._route_fn(ex.A * FC, FC)[1]


@pytest.mark.parametrize("lowered", [_lowered_level_step,
                                     _lowered_mesh_superstep],
                         ids=["level_step", "mesh_superstep"])
def test_engine_steps_sort_no_seen_sized_block(lowered):
    """The engines merge a level's candidates into the seen set through
    `_rank_merge` and nothing else: no sort in the lowered step has
    SC + C operands (level engine; SC + R on a mesh shard, R the
    exchanged block), the signature of the full-sort merge PR 28
    deleted — nor any other length that follows the seen capacity."""
    SC, FC = 1 << 14, 64
    text, block = lowered(SC, FC)
    found = _SORT.findall(text)
    assert found and len(found) == len(
        re.findall(r'stablehlo\.sort"?\(', text))
    lengths = {int(t.split("x")[0]) for m in found
               for t in re.findall(r"tensor<([^>]*)>", m)}
    assert block < SC and SC + block not in lengths, lengths
    assert max(lengths) <= block, (lengths, block)
