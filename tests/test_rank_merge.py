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
step and the mesh superstep.

Since ISSUE 29 the merge's tail follows what is live too: seen2 is the
incoming table with the blocks that hold a live row rebuilt in place
(a loop with a traced trip count), the index scatters push the valid
blocks of sorted rows alone; the cases below land seen_count2 on every
kind of block border over consecutive merges and hold the rows past
the built blocks to what came in, and a fourth lowering guard keeps a
whole-table gather out of the three engines' programs.

Since ISSUE 45 a block of SORTED queries searches a window of the
table where the table has more rows than W = `_probe_window_rows(SC)`
and the block's answers span fewer: the last section lowers W to give
toy tables a window and holds the windowed answers to the whole-table
probe's (the form up to PR 44, kept here alone) and to bisection, at
every border of the window, and the choice each block makes to the rule
as arithmetic.

Since ISSUE 48 a block of the build takes its NEW rows from a window as
well, where the level has more key slots than `_BUILD_WHOLE_KEYS`: the
new keys compacted once, a slice of B of them a block.  The last
section lowers that floor to give the toy shapes the window form and
holds its answers to the whole form's (the function up to PR 47, kept
here alone), to the row-scatter oracle's and to numpy's, over the
scenarios above and at the window's own borders; at or under the floor
the function must lower to the text it had."""

import functools
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from jaxmc.backend import bfs  # noqa: E402
from jaxmc.backend.bfs import (  # noqa: E402
    SENTINEL, _build_form, _lower_bound, _lsd_sort, _merge_block_rows,
    _merge_blocks, _probe_block_rows, _probe_blocks, _probe_by_block,
    _probe_window_rows, _rank_merge, _seen_probe, _sort_rung_index,
    _sort_rungs)


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


# what both references answer (the oracle predates `probe_blocks` and
# `merge_blocks`)
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
# to SC 2^15), four even blocks, three blocks that overhang SC
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
        B = min(SC, BLOCKS[blocks])
        assert int(got["merge_blocks"]) == min(
            -(-int(got["seen_count2"]) // B), -(-SC // B))
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


# ---- the tail sized by what is live (ISSUE 29) ----
#
# seen2 is built MB = 16 rows at a time (SC = 64: four blocks), only the
# blocks that hold a live row after the merge.  (seen rows before the
# first merge, new keys of three consecutive merges): seen_count2 lands
# at 0, inside a block, on a block's edge, at SC, past SC
MB = 16
LANDINGS = {"zero": (0, (0, 0, 0)),
            "mid_block": (5, (7, 4, 7)),        # 12, 16, 23
            "block_edge": (0, (16, 10, 6)),     # 16, 26, 32
            "at_SC": (30, (20, 0, 14)),         # 50, 50, 64
            "past_SC": (40, (10, 10, 20))}      # 50, 60, 80: 16 dropped


@pytest.fixture
def _toy_merge_blocks(monkeypatch):
    monkeypatch.setattr(bfs, "_MERGE_BLOCK_ROWS", MB)
    assert _merge_block_rows(SC) == MB


def _levels(landing, K, rng):
    """(table, seen count, keys of three merges).  The table's tail is
    what the engines seed and grow it with: SENTINEL in EVERY lane, the
    validity lane too — a block the merge builds writes lane 1 there, a
    block it skips leaves the row as it came."""
    n0, news = LANDINGS[landing]
    uni = _lexsorted(rng.integers(-99, 100, size=(8 * SC, K - 1))
                     .astype(np.int32))
    uni = uni[rng.permutation(len(uni))]
    table = np.full((SC, K), SENTINEL, np.int32)
    table[:n0, 0] = 0
    table[:n0, 1:] = _lexsorted(uni[:n0])
    have, levels = n0, []
    for nw in news:
        pool = uni[:have + nw]
        n_dup = int(rng.integers(0, N - nw - 4)) if len(pool) else 0
        words = np.concatenate(
            [uni[have:have + nw],
             pool[rng.integers(0, max(len(pool), 1), n_dup)]])
        slots = rng.permutation(N)[:len(words)]
        valid = np.zeros(N, bool)
        valid[slots] = True
        kwords = np.zeros((N, K - 1), np.int32)
        kwords[slots] = words
        levels.append(_keys(kwords, valid, K))
        have += nw
    return table, n0, levels


def _np_live_merge(table, count, keys, K):
    """What _rank_merge answers for a table whose tail is not (1,
    SENTINEL): the set union in the blocks built, the incoming rows
    past them."""
    ref = _np_reference(table, count, keys, SC, K)
    blocks = -(-min(ref["seen_count2"], SC) // MB)
    seen2 = table.copy()
    seen2[:blocks * MB] = ref["seen2"][:blocks * MB]
    return dict(ref, seen2=seen2, merge_blocks=blocks)


def _check_level(got, table, count, keys, K, tag):
    want = _np_live_merge(table, count, keys, K)
    for name in ANSWER + ("merge_blocks",):
        assert np.array_equal(np.asarray(got[name]), want[name]), (name, tag)
    assert want["merge_blocks"] == _merge_blocks(want["seen_count2"], SC) \
        == int(_merge_blocks(jnp.int32(want["seen_count2"]), SC))
    return want


_MERGE = jax.jit(_rank_merge, static_argnums=(3, 4, 5, 6))


@pytest.mark.parametrize("landing", LANDINGS)
@pytest.mark.parametrize("multikey", [False, True])
@pytest.mark.parametrize("K", [3, 5])
def test_merge_builds_the_live_blocks_over_three_levels(
        K, multikey, landing, _toy_merge_blocks):
    for trial in range(3):
        rng = np.random.default_rng(
            [K, int(multikey), list(LANDINGS).index(landing), trial, 29])
        table, count, levels = _levels(landing, K, rng)
        for lvl, keys in enumerate(levels):
            got = _MERGE(jnp.asarray(table), jnp.int32(count),
                         jnp.asarray(keys), N, SC, K, multikey)
            want = _check_level(got, table, count, keys, K, (trial, lvl))
            table, count = want["seen2"], want["seen_count2"]
        assert count == LANDINGS[landing][0] + sum(LANDINGS[landing][1])
        # rows past the last block built are the seed's, lane and all
        built = want["merge_blocks"] * MB
        assert np.all(table[built:] == SENTINEL)
        assert np.all(table[min(count, SC):built, 0] == 1)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _three_levels_in_a_loop(table, count, keys3, K, multikey):
    """The resident engine's form: the merges of consecutive levels
    inside one lax.while_loop, table and count in its carry."""
    def body(carry):
        lvl, table, count, blocks = carry
        rm = _rank_merge(table, count, keys3[lvl], N, SC, K, multikey)
        return (lvl + 1, rm["seen2"], rm["seen_count2"],
                blocks.at[lvl].set(rm["merge_blocks"]))

    return lax.while_loop(lambda c: c[0] < 3, body,
                          (jnp.int32(0), table, count,
                           jnp.zeros((3,), jnp.int32)))[1:]


@pytest.mark.parametrize("landing", LANDINGS)
def test_merge_inside_a_while_loop(landing, _toy_merge_blocks):
    K = 5
    rng = np.random.default_rng([list(LANDINGS).index(landing), 290])
    table, count, levels = _levels(landing, K, rng)
    seen2, count2, blocks = _three_levels_in_a_loop(
        jnp.asarray(table), jnp.int32(count), jnp.asarray(np.stack(levels)),
        K, False)
    want_blocks = []
    for keys in levels:
        want = _np_live_merge(table, count, keys, K)
        table, count = want["seen2"], want["seen_count2"]
        want_blocks.append(want["merge_blocks"])
    assert np.array_equal(np.asarray(seen2), table)
    assert int(count2) == count
    assert list(np.asarray(blocks)) == want_blocks


@pytest.mark.parametrize("shift", [0, 1])
def test_merge_under_shard_map(shift, _toy_merge_blocks):
    """A mesh shard's form: four shards, each its own table, counts and
    landing, so each bounds its loops with its own traced counts (the
    loops' carries must be device-varying: fori_loop refuses others)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    D, K = 4, 5
    if len(jax.devices()) < D:
        pytest.skip("needs four (virtual) devices")
    mesh = Mesh(np.array(jax.devices()[:D]), ("d",))

    def shard(table, count, keys):
        rm = _rank_merge(table[0], count[0], keys[0], N, SC, K, True)
        return tuple(rm[name][None] for name in ANSWER + ("merge_blocks",))

    step = jax.jit(shard_map(shard, mesh=mesh, in_specs=(P("d"),) * 3,
                             out_specs=(P("d"),) * 5))
    names = [list(LANDINGS)[(d + shift) % len(LANDINGS)] for d in range(D)]
    cases = [_levels(nm, K, np.random.default_rng([d, shift, 2900]))
             for d, nm in enumerate(names)]
    tables = [c[0] for c in cases]
    counts = [c[1] for c in cases]
    for lvl in range(3):
        keys = [c[2][lvl] for c in cases]
        got = step(jnp.asarray(np.stack(tables)),
                   jnp.asarray(counts, jnp.int32),
                   jnp.asarray(np.stack(keys)))
        for d in range(D):
            got_d = {name: np.asarray(g)[d]
                     for name, g in zip(ANSWER + ("merge_blocks",), got)}
            want = _check_level(got_d, tables[d], counts[d], keys[d], K,
                                (names[d], lvl))
            tables[d], counts[d] = want["seen2"], want["seen_count2"]


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
        # one block of QB sorted rows a turn (ISSUE 29), one i32 each:
        # no [SC, K] or [N, K-1] row updates
        assert indices == f"{QB}x1xi32", found
        assert updates == f"{QB}xi32", found
        assert operand in (f"{N}xi32", f"{SC}xi32"), found
    # the guard has teeth: the formulation it replaced fails it
    old = _scatters(_scatter_rank_merge, K, multikey)
    assert any("x" in u.split("xi32")[0] for _, _, u in old), old


@pytest.mark.parametrize("multikey", [False, True])
@pytest.mark.parametrize("K", [3, 5])
def test_rank_merge_builds_seen2_in_a_traced_loop(K, multikey,
                                                  _toy_merge_blocks):
    """Whole K-word rows are gathered by the build alone (the probe
    reads the K-1 data words): MB rows from an MB-row window of the
    table and MB from the sorted keys, inside ONE loop whose trip count
    is traced — a `while`; the lax.map over all SC / MB blocks that
    ISSUE 29 replaced is a `scan`, which _gathers does not count."""
    shapes = (jax.ShapeDtypeStruct((SC, K), jnp.int32),
              jax.ShapeDtypeStruct((), jnp.int32),
              jax.ShapeDtypeStruct((N, K), jnp.int32))
    jaxpr = jax.make_jaxpr(
        lambda s, c, k: _rank_merge(s, c, k, N, SC, K, multikey))(*shapes)
    rows = sorted((loops, operand, result)
                  for loops, operand, result in _gathers(jaxpr.jaxpr)
                  if operand[1] == K)
    assert rows == [(1, (MB, K), (MB, K)), (1, (N, K), (MB, K))], rows
    # the one scan there may be is the LSD chain's (ISSUE 42: the five
    # passes as one sort in a loop); it gathers nothing
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name == "scan":
            inner = [e.primitive.name
                     for e in eqn.params["jaxpr"].jaxpr.eqns]
            assert "sort" in inner and "gather" not in inner, inner
            assert not multikey


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


def _level_step(SC, FC):
    """(engine, the level engine's step program, its arguments, the
    merge's key slots N)."""
    from jaxmc.backend.bfs import TpuExplorer
    ex = TpuExplorer(_constoy())
    i32 = jnp.int32
    return ex, ex._get_step(SC, FC).__wrapped__, (
        jnp.zeros((SC, ex.K), i32), i32(0),
        jnp.zeros((FC, ex.PW), i32), i32(0)), ex.A * FC


def _resident_run(SC, FC):
    """The same for the resident engine's while_loop program."""
    from jaxmc.backend.bfs import TpuExplorer
    ex = TpuExplorer(_constoy())
    i32 = jnp.int32
    AccCap, VC, CH = 4 * FC, 2 * FC, FC
    return ex, ex._get_resident_run(SC, FC, AccCap, VC, CH).__wrapped__, (
        jnp.zeros((SC, ex.K), i32), i32(0), jnp.zeros((FC, ex.PW), i32),
        i32(0), i32(0), i32(0), i32(0), i32(0), i32(0), i32(1)), AccCap


def _mesh_superstep(SC, FC):
    """The same for the mesh engine's superstep (four shards)."""
    from jax.sharding import Mesh
    from jaxmc.backend.mesh import MeshExplorer
    D, TRL, VC = 4, 16, 2 * FC
    ex = MeshExplorer(_constoy(), exchange="a2a",
                      mesh=Mesh(np.array(jax.devices()[:D]), ("d",)))
    i32 = jnp.int32
    return ex, ex._get_mesh_resident_step(SC, FC, TRL, VC).__wrapped__, (
        jnp.zeros((D, SC, ex.K), i32), jnp.zeros((D,), i32),
        jnp.zeros((D, FC, ex.PW), i32), jnp.zeros((D,), i32),
        jnp.zeros((D, TRL, FC, ex.PW), i32), jnp.zeros((D, TRL, FC), i32),
        i32(0), i32(0), i32(0), i32(0)), VC


def _lowered_level_step(SC, FC):
    ex, fn, args, _ = _level_step(SC, FC)
    return fn.lower(*args).as_text(), ex.A * FC


def _lowered_mesh_superstep(SC, FC):
    ex, fn, args, _ = _mesh_superstep(SC, FC)
    return fn.lower(*args).as_text(), ex._route_fn(ex.A * FC, FC)[1]


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


@pytest.mark.parametrize("program,outer", [
    (_level_step, 0), (_resident_run, 1), (_mesh_superstep, 1)],
    ids=["level_step", "resident_run", "mesh_superstep"])
def test_engine_programs_build_seen2_by_block_in_a_traced_loop(
        program, outer, monkeypatch):
    """Every engine's program builds the merged table a block of B rows
    at a time inside a loop of its own — a `while`, what a fori_loop
    with a TRACED trip count lowers to (a static count gives a `scan`,
    which _gathers does not count), `outer` levels loops round it — and
    gathers no table-sized block anywhere: the fixed pass over all SC
    slots a level (ISSUE 29) stays out."""
    SC, FC = 1 << 14, 64
    monkeypatch.setattr(bfs, "_MERGE_BLOCK_ROWS", 1 << 10)
    B = _merge_block_rows(SC)
    assert B == 1 << 10
    ex, fn, args, n_keys = program(SC, FC)
    K = ex.K
    found = _gathers(jax.make_jaxpr(fn)(*args).jaxpr)
    assert found
    for loops, operand, result in found:
        assert result[0] < SC, (operand, result)
    # the window of the table and the sorted keys, once a block each
    window = [loops for loops, operand, result in found
              if operand == (B, K) and result == (B, K)]
    fresh = [loops for loops, operand, result in found
             if operand == (n_keys, K) and result == (B, K)]
    assert window == fresh == [outer + 1], (window, fresh)


# ---- the sort sized by the live prefix (ISSUE 42) ----
#
# N = 128 key slots and a floor of 8: the ladder 128, 64, 32, 16, 8.  A
# level's valid keys sit in keys[0:n_prefix] — with invalid rows AMONG
# them, the form the POR filter leaves (bfs.py, `keys_c = jnp.where(
# keep_c ...)`) — and every row past the prefix is what the accumulator
# starts as, SENTINEL in every lane.
LN, LSC, RUNG_MIN = 128, 256, 8
RUNGS = (128, 64, 32, 16, 8)
PREFIXES = sorted({n for r in RUNGS for n in (r - 1, r, r + 1)
                   if n <= LN} | {0, 1})


def test_sort_rungs_are_static_in_the_key_shape(monkeypatch):
    monkeypatch.setattr(bfs, "_SORT_RUNG_MIN", RUNG_MIN)
    assert bfs._sort_rungs(LN) == RUNGS
    assert bfs._sort_unit(LN) == 8
    # not a power of two: halves rounded down, none under the floor
    assert bfs._sort_rungs(100) == (100, 50, 25, 12)
    assert bfs._sort_unit(100) == 1
    # shorter than two floors: one rung, and _rank_merge has no switch
    assert bfs._sort_rungs(15) == (15,)
    assert bfs._sort_unit(15) == 15
    for n in (LN, 100):
        rungs = bfs._sort_rungs(n)
        for p in range(n + 9):
            i = bfs._sort_rung_index(p, n)
            assert int(bfs._sort_rung_index(jnp.int32(p), n)) == i
            # the smallest rung that holds the prefix; all n past it
            assert rungs[i] >= min(p, n)
            assert i == len(rungs) - 1 or rungs[i + 1] < p
    monkeypatch.undo()
    # the benchmark cells' accumulators: 3, 7 and 9 rungs down to 2^15
    assert [len(bfs._sort_rungs(1 << s)) for s in (17, 21, 23)] \
        == [3, 7, 9]
    assert bfs._sort_rungs(1 << 17)[-1] == bfs._sort_unit(1 << 23) \
        == 1 << 15


def _prefix_case(n_prefix, K, rng):
    """(seen table, seen count, keys): duplicates, keys already seen
    and invalid rows inside keys[0:n_prefix], nothing valid past it."""
    words = rng.integers(-3, 4, size=(LN, K - 1)).astype(np.int32)
    pool = _lexsorted(rng.integers(-3, 4, size=(LSC, K - 1))
                      .astype(np.int32))
    n_seen = min(LSC // 4, len(pool))
    swords = pool[np.sort(rng.choice(len(pool), n_seen, replace=False))]
    valid = (rng.random(LN) < 0.8) & (np.arange(LN) < n_prefix)
    keys = _keys(words, valid, K)
    # masked rows inside the prefix carry lane 1; the rows no chunk
    # wrote carry SENTINEL there too
    keys[n_prefix:, 0] = SENTINEL
    return _table(swords, LSC, K), n_seen, keys


@pytest.mark.parametrize("n_prefix", PREFIXES)
@pytest.mark.parametrize("K", [3, 5])
def test_rank_merge_sorts_the_rung_that_holds_the_prefix(
        K, n_prefix, monkeypatch):
    """With n_prefix the merge answers what it answers without, and
    sorts the one rung the rule names."""
    monkeypatch.setattr(bfs, "_SORT_RUNG_MIN", RUNG_MIN)
    monkeypatch.setattr(bfs, "_MERGE_BLOCK_ROWS", 64)
    assert bfs._sort_rungs(LN) == RUNGS
    laddered = jax.jit(lambda s, c, k, n: _rank_merge(
        s, c, k, LN, LSC, K, n_prefix=n))
    plain = jax.jit(lambda s, c, k: _rank_merge(s, c, k, LN, LSC, K))
    for trial in range(3):
        rng = np.random.default_rng([K, n_prefix, trial, 42])
        seen, n_seen, keys = _prefix_case(n_prefix, K, rng)
        # a promise kept with room to spare changes the rung alone
        for promised in {n_prefix, min(n_prefix + 5, LN), LN + 3}:
            got = laddered(jnp.asarray(seen), jnp.int32(n_seen),
                           jnp.asarray(keys), jnp.int32(promised))
            want = plain(jnp.asarray(seen), jnp.int32(n_seen),
                         jnp.asarray(keys))
            for name in ANSWER + ("probe_blocks", "merge_blocks"):
                assert np.array_equal(np.asarray(got[name]),
                                      np.asarray(want[name])), \
                    (name, trial, promised)
            assert int(want["sort_slots"]) == LN
            assert int(got["sort_slots"]) == RUNGS[
                bfs._sort_rung_index(promised, LN)]
        ref = _np_reference(seen, n_seen, keys, LSC, K)
        for name in ANSWER:
            assert np.array_equal(np.asarray(got[name]), ref[name]), name


def _conds(jaxpr, scope=""):
    """The name stack of every `cond` (lax.switch, lax.cond) under a
    jaxpr."""
    out = []
    for eqn in jaxpr.eqns:
        stack = scope + "/" + str(eqn.source_info.name_stack)
        if eqn.primitive.name == "cond":
            out.append(stack)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _conds(sub, stack)
    return out


@pytest.mark.parametrize("window", [None, 1 << 10],
                         ids=["no_window", "windowed"])
@pytest.mark.parametrize("program,ladders", [
    (_level_step, 0), (_resident_run, 1), (_mesh_superstep, 0)],
    ids=["level_step", "resident_run", "mesh_superstep"])
def test_the_sort_ladder_is_in_the_resident_program_alone(
        program, ladders, window, monkeypatch):
    """The resident level's candidates are a prefix of its accumulator
    and its merge switches between the rungs, once, under
    jaxmc.merge.sort; the level and the mesh engines' key slots are no
    prefix, they pass no n_prefix and their programs branch nowhere.
    Where a table has more rows than the probe's window (ISSUE 45; the
    floor lowered here, no engine's tables but the resident's are that
    large) a program holds exactly one conditional more, under
    jaxmc.merge.probe: a block's choice between the window and the
    whole table."""
    monkeypatch.setattr(bfs, "_SORT_RUNG_MIN", 32)
    if window:
        monkeypatch.setattr(bfs, "_PROBE_WINDOW_ROWS", window)
    ex, fn, args, n_keys = program(1 << 14, 64)
    found = _conds(jax.make_jaxpr(fn)(*args).jaxpr)
    assert len(found) == ladders + bool(window), found
    assert sum("jaxmc.merge.probe" in stack for stack in found) \
        == bool(window), found
    assert sum("jaxmc.merge.sort" in stack
               and "jaxmc.merge.probe" not in stack
               for stack in found) == ladders, found
    if ladders:
        rungs = bfs._sort_rungs(n_keys)
        assert len(rungs) >= 3
        # ONE sort a rung: the LSD passes are a loop over one sort of
        # the K key columns and the row index (XLA:TPU's compile
        # seconds follow the sort instructions of a program)
        text = fn.lower(*args).as_text()
        chains = sorted(
            (int(types[0].split("x")[0]) for types in (
                re.findall(r"tensor<([^>]*)>", m)
                for m in _SORT.findall(text))
             if len(types) == ex.K + 1), reverse=True)
        assert tuple(chains) == rungs, (rungs, chains)


# ---- the probe searches a window of the table (ISSUE 45) ----
#
# A table of WSC = 256 rows, a window floor of WW = 32, the file's 48
# query slots in three blocks of QB = 16.  The table's j-th row holds
# 10 * j in its last word, so the query 10 * j answers (found, j) and
# 10 * j + 5 answers (not found, j + 1).

WSC, WW = 256, 32


def _whole_table_probe(seen, seen_count, keys, SC, n_live, sorted_keys):
    """`_seen_probe` as it stood up to PR 44, word for word: every block
    against the whole table.  The windowed probe's oracle, and the text
    a table of no more than W rows must still lower to."""
    n = keys.shape[0]
    words = keys[:, 1:]
    seen_words = seen[:, 1:]
    qb = _probe_block_rows(n)
    live = n if n_live is None else jnp.minimum(n_live, n)
    every = bfs._PROBE_SAMPLE
    at_s = np.append(np.arange(0, qb, every), qb - 1)

    def block(b, out):
        found, lb = out
        at = jnp.minimum(b * qb, n - qb)
        q = lax.dynamic_slice(words, (at, 0), (qb, words.shape[1]))
        if sorted_keys:
            lb_s = _lower_bound(seen_words, seen_count, q[at_s], SC)
            lb_s = jnp.where(at + at_s < live, lb_s, seen_count)
            lb_b = _lower_bound(seen_words, seen_count, q, SC,
                                jnp.repeat(lb_s[:-1], every)[:qb],
                                jnp.repeat(lb_s[1:], every)[:qb])
        else:
            lb_b = _lower_bound(seen_words, seen_count, q, SC)
        at_lb = jnp.take(seen_words, jnp.clip(lb_b, 0, SC - 1), axis=0)
        found_b = (lb_b < seen_count) & jnp.all(at_lb == q, axis=1)
        return (lax.dynamic_update_slice(found, found_b, (at,)),
                lax.dynamic_update_slice(lb, lb_b, (at,)))

    lb0 = words[:, 0] - words[:, 0]
    return lax.fori_loop(0, _probe_blocks(live, n), block,
                         (lb0 != 0, lb0))


def _np_window_blocks(seen, n_seen, keys, live, SC, W):
    """The blocks that take the window, by the rule as arithmetic: a
    block's first and last LIVE query bisected, the window started at
    the first answer and clamped to the table's end, taken where the
    row AT the last answer lies inside."""
    n = len(keys)
    qb = _probe_block_rows(n)
    lb = _np_probe(seen, n_seen, keys)[1]
    took = []
    for b in range(-(-min(live, n) // qb)):
        at = min(b * qb, n - qb)
        w_lo, w_hi = lb[at], lb[min(live - 1, at + qb - 1)]
        w0 = min(max(w_lo, 0), SC - W)
        took.append(bool(w_hi < w0 + W))
    return took


def _tens(K, values):
    """Key words whose last is each value and whose others are 0."""
    w = np.zeros((len(values), K - 1), np.int32)
    w[:, -1] = values
    return w


def _sorted_keys(words, live, n, K):
    """n key slots: `words` (ascending) as the live prefix, the rest
    invalid and SENTINEL, as _rank_merge's sort leaves them."""
    assert len(words) == live
    return _keys(np.concatenate([
        words, np.zeros((n - live, K - 1), np.int32)]),
        np.arange(n) < live, K)


def _window_case(name, K=5):
    """(table, n_seen, keys, live, the blocks that take the window)."""
    rows = np.arange(WSC) * 10
    n_seen, live = 200, N
    first = lambda j, m: list(rows[j:j + m])   # m hits from row j on
    if name == "first_row":
        # block 0 starts AT its window's first row, 40, and hits it
        q = first(40, 16) + first(80, 16) + first(120, 16)
        took = [True, True, True]
    elif name == "last_row":
        # block 0's last answer is the window's last row, 40 + W - 1
        q = first(40, 15) + [rows[40 + WW - 1]] + first(80, 32)
        took = [True, True, True]
    elif name == "one_past":
        # the last queries lie past every row: lb == seen_count, inside
        # the window of a block that starts 22 rows before the end
        n_seen = 72
        q = first(8, 16) + first(30, 16) + first(50, 12) + [999] * 4
        took = [True, True, True]
    elif name == "span_W":
        # a miss before row 40 to a miss before row 40 + W - 1: the
        # answers span exactly W rows, the window
        q = [rows[40] - 5] + first(40, 14) + [rows[40 + WW - 1] - 5] + \
            first(80, 32)
        took = [True, True, True]
    elif name == "span_W_plus_1":
        # ... and one row more: the whole table
        q = [rows[40] - 5] + first(40, 14) + [rows[40 + WW] - 5] + \
            first(80, 32)
        took = [False, True, True]
    elif name == "clamped_end":
        # a full table; the last block starts 16 rows before its end, so
        # its window is clamped to [SC - W, SC)
        n_seen = WSC
        q = first(100, 16) + first(200, 16) + first(WSC - 16, 10) + \
            [rows[WSC - 6] + 5] * 6
        took = [True, True, True]
    elif name == "partial_last_block":
        # four live rows in the last block searched: the SENTINEL rows
        # after them would answer seen_count, 150 rows on
        live = 20
        q = first(40, 16) + first(60, 4)
        took = [True, True]
    elif name == "duplicates_across_a_border":
        # one hit five times, over the border of blocks 0 and 1; one
        # miss six times over that of blocks 1 and 2
        q = first(40, 14) + [rows[54]] * 5 + first(55, 9) + \
            [rows[63] + 5] * 6 + first(64, 14)
        took = [True, True, True]
    else:
        raise AssertionError(name)
    assert len(q) == live and sorted(q) == list(q)
    return (_table(_tens(K, rows[:n_seen]), WSC, K), n_seen,
            _sorted_keys(_tens(K, q), live, N, K), live, took)


WINDOW_CASES = ("first_row", "last_row", "one_past", "span_W",
                "span_W_plus_1", "clamped_end", "partial_last_block",
                "duplicates_across_a_border")


def _windowed_probe():
    """A fresh jit a test: the window's floor is read when a shape is
    first traced, and jit caches by function."""
    return jax.jit(
        lambda s, c, k, n: _probe_by_block(s, c, k, WSC, n, True))


def _check_window(seen, n_seen, keys, live, took=None):
    found, lb, blocks = _windowed_probe()(
        jnp.asarray(seen), jnp.int32(n_seen), jnp.asarray(keys),
        jnp.int32(live))
    whole_found, whole_lb = jax.jit(
        lambda s, c, k, n: _whole_table_probe(s, c, k, WSC, n, True))(
        jnp.asarray(seen), jnp.int32(n_seen), jnp.asarray(keys),
        jnp.int32(live))
    want_found, want_lb = _np_probe(seen, n_seen, keys)
    rule = _np_window_blocks(seen, n_seen, keys, live, WSC, WW)
    if took is not None:
        assert rule == took, rule
    assert int(blocks) == sum(rule)
    for got, whole, want in ((found, whole_found, want_found),
                             (lb, whole_lb, want_lb)):
        assert np.array_equal(np.asarray(got)[:live],
                              np.asarray(whole)[:live])
        assert np.array_equal(np.asarray(got)[:live], want[:live])
    return rule


@pytest.fixture
def _toy_window(monkeypatch):
    monkeypatch.setattr(bfs, "_PROBE_WINDOW_ROWS", WW)
    assert _probe_window_rows(WSC) == WW and _probe_window_rows(WW) == WW


@pytest.mark.parametrize("case", WINDOW_CASES)
def test_windowed_probe_at_the_windows_borders(case, _toy_window):
    seen, n_seen, keys, live, took = _window_case(case)
    _check_window(seen, n_seen, keys, live, took)
    if case == "one_past":
        assert _np_probe(seen, n_seen, keys)[1][live - 1] == n_seen
    if case == "clamped_end":
        assert _np_probe(seen, n_seen, keys)[1][2 * QB] > WSC - WW


@pytest.mark.parametrize("trial", range(4))
def test_windowed_probe_of_exact_keys_in_a_clustered_table(trial,
                                                           _toy_window):
    """K = 3, the level engine's exact keys: nothing spreads them, so
    most of the table sits in one narrow range of keys and a block of
    queries over it spans far more rows than W.  Some blocks take the
    window and some the whole table, and every answer is bisection's."""
    K = 3
    rng = np.random.default_rng([trial, 450])
    n_seen = int(rng.integers(WSC // 2, WSC + 1))
    dense = np.stack([np.full(4 * WSC, 3), rng.integers(
        0, 400, 4 * WSC)], axis=1)
    sparse = rng.integers(-40, 40, size=(WSC // 4, K - 1))
    pool = _lexsorted(np.concatenate([dense, sparse]).astype(np.int32))
    swords = pool[np.sort(rng.choice(len(pool), n_seen, replace=False))]
    live = int(rng.integers(2 * QB + 1, N + 1))
    qwords = np.concatenate([
        swords[rng.choice(n_seen, live // 2)],
        rng.integers(-40, 40, size=(live - live // 2, K - 1))])
    qwords = qwords[np.lexsort(tuple(
        qwords[:, j] for j in reversed(range(K - 1))))].astype(np.int32)
    rule = _check_window(_table(swords, WSC, K), n_seen,
                         _sorted_keys(qwords, live, N, K), live)
    if trial == 0:
        assert True in rule and False in rule, rule


def test_a_table_of_no_more_than_W_rows_lowers_to_the_form_it_had(
        monkeypatch):
    """W >= SC: no window and no branch — the text of the probe up to
    PR 44, which the level engine's step, the mesh's shards and the
    small resident tables keep."""
    def lowered(probe, sorted_keys):
        def merge_probe(seen, count, keys, n_live):
            return tuple(probe(seen, count, keys, WSC, n_live,
                               sorted_keys))[:2]
        return jax.jit(merge_probe).lower(
            jax.ShapeDtypeStruct((WSC, 5), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((N, 5), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)).as_text()

    for floor in (WSC, 2 * WSC):
        monkeypatch.setattr(bfs, "_PROBE_WINDOW_ROWS", floor)
        for sorted_keys in (True, False):
            text = lowered(_probe_by_block, sorted_keys)
            assert text == lowered(_whole_table_probe, sorted_keys)
            assert "stablehlo.case" not in text and \
                "stablehlo.if" not in text
    # ... and with a window the sorted form branches, the unsorted form
    # (the POR filter's) never
    monkeypatch.setattr(bfs, "_PROBE_WINDOW_ROWS", WW)
    assert lowered(_probe_by_block, False) == \
        lowered(_whole_table_probe, False)
    text = lowered(_probe_by_block, True)
    assert "stablehlo.case" in text or "stablehlo.if" in text


def _window_levels(K, rng):
    """Three levels of candidates for a table of WSC rows that starts
    with a few: the windows move as the table fills."""
    pool = _lexsorted(rng.integers(-30, 31, size=(8 * WSC, K - 1))
                      .astype(np.int32))
    start = pool[np.sort(rng.choice(len(pool), 40, replace=False))]
    levels = []
    for lvl in range(3):
        live = int(rng.integers(QB + 1, N + 1))
        valid = np.zeros(N, bool)
        valid[rng.choice(N, live, replace=False)] = True
        levels.append(_keys(pool[rng.choice(len(pool), N)], valid, K))
    return _table(start, WSC, K), len(start), levels


def _np_window_merges(table, count, levels, K):
    """(final table, final count, windowed blocks a level) by numpy."""
    blocks = []
    for keys in levels:
        order = np.lexsort(tuple(keys[:, j] for j in reversed(range(K))))
        skeys = keys[order]
        live = int((skeys[:, 0] == 0).sum())
        blocks.append(sum(_np_window_blocks(table, count, skeys, live,
                                            WSC, WW)))
        want = _np_reference(table, count, keys, WSC, K)
        table, count = want["seen2"], want["seen_count2"]
    return table, count, blocks


def test_windowed_merge_inside_a_while_loop(_toy_window):
    K = 5
    rng = np.random.default_rng(4501)
    table, count, levels = _window_levels(K, rng)

    @jax.jit
    def run(table, count, keys3):
        def body(carry):
            lvl, table, count, blocks = carry
            rm = _rank_merge(table, count, keys3[lvl], N, WSC, K)
            return (lvl + 1, rm["seen2"], rm["seen_count2"],
                    blocks.at[lvl].set(rm["window_blocks"]))
        return lax.while_loop(lambda c: c[0] < 3, body,
                              (jnp.int32(0), table, count,
                               jnp.zeros((3,), jnp.int32)))[1:]

    seen2, count2, blocks = run(jnp.asarray(table), jnp.int32(count),
                                jnp.asarray(np.stack(levels)))
    want_table, want_count, want_blocks = _np_window_merges(
        table, count, levels, K)
    assert int(count2) == want_count
    assert np.array_equal(np.asarray(seen2)[:want_count],
                          want_table[:want_count])
    assert list(np.asarray(blocks)) == want_blocks
    assert sum(want_blocks) > 0


def test_windowed_merge_under_shard_map(_toy_window):
    """Four shards, each its own table and candidates: each block's
    choice is its own shard's (a device-varying predicate)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    D, K = 4, 5
    if len(jax.devices()) < D:
        pytest.skip("needs four (virtual) devices")
    mesh = Mesh(np.array(jax.devices()[:D]), ("d",))

    def shard(table, count, keys):
        rm = _rank_merge(table[0], count[0], keys[0], N, WSC, K, True)
        return tuple(rm[name][None] for name in
                     ("seen2", "seen_count2", "window_blocks"))

    step = jax.jit(shard_map(shard, mesh=mesh, in_specs=(P("d"),) * 3,
                             out_specs=(P("d"),) * 3))
    cases = [_window_levels(K, np.random.default_rng([d, 4502]))
             for d in range(D)]
    tables = [c[0] for c in cases]
    counts = [c[1] for c in cases]
    total = 0
    for lvl in range(3):
        keys = [c[2][lvl] for c in cases]
        seen2, count2, blocks = step(
            jnp.asarray(np.stack(tables)), jnp.asarray(counts, jnp.int32),
            jnp.asarray(np.stack(keys)))
        for d in range(D):
            t, n, b = _np_window_merges(tables[d], counts[d], [keys[d]], K)
            assert int(count2[d]) == n
            assert np.array_equal(np.asarray(seen2)[d][:n], t[:n])
            assert int(blocks[d]) == b[0]
            tables[d], counts[d] = t, n
            total += b[0]
    assert total > 0


# ---- the build's new rows from a window (ISSUE 48) ----
#
# N = 48 key slots and QB = 16: the compaction of a level's new keys
# runs ceil(new_count / 16) blocks.  The floor at 0 gives every shape
# of this file the window form; the default leaves them the whole form.


def _whole_rank_merge(seen, seen_count, keys, N, SC, K, multikey=False,
                n_prefix=None):
    """_rank_merge as it was up to PR 47, kept verbatim (comments
    apart): every block of the build gathers its new rows from all N
    sorted keys.  The oracle of the window form's answers, and the text
    a program of no more than _BUILD_WHOLE_KEYS key slots must still
    lower to."""
    sidx = jnp.arange(N, dtype=jnp.int32)
    rungs = (N,) if n_prefix is None or multikey else _sort_rungs(N)
    sort_slots = N
    with jax.named_scope("jaxmc.merge.sort"):
        if multikey:
            res = lax.sort(tuple(keys[:, j] for j in range(K)) + (sidx,),
                           num_keys=K, is_stable=True)
            kc = list(res[:K])
            sidx_s = res[K]
        elif len(rungs) == 1:
            kc, ec = _lsd_sort([keys[:, j] for j in range(K)], [sidx])
            sidx_s = ec[0]
        else:
            def sort_prefix(r):
                def branch(cols):
                    kc, ec = _lsd_sort([c[:r] for c in cols[:K]],
                                       [cols[K][:r]])
                    return tuple(
                        lax.dynamic_update_slice(c, s, (0,))
                        for c, s in zip(cols, kc + ec))
                return branch

            rung = _sort_rung_index(n_prefix, N)
            res = lax.switch(rung, [sort_prefix(r) for r in rungs],
                             tuple(keys[:, j] for j in range(K)) + (sidx,))
            kc = list(res[:K])
            sidx_s = res[K]
            sort_slots = jnp.asarray(rungs, jnp.int32)[rung]
    skeys = jnp.stack(kc, axis=1)
    skeys, seen = lax.optimization_barrier((skeys, seen))
    svalid = skeys[:, 0] == 0
    neq_prev = jnp.concatenate([
        jnp.array([True]),
        jnp.any(skeys[1:] != skeys[:-1], axis=1)])

    n_live = jnp.sum(svalid, dtype=jnp.int32)
    found, lb, window_blocks = _probe_by_block(seen, seen_count, skeys, SC,
                                               n_live, sorted_keys=True)
    new = svalid & ~found & neq_prev
    new_count = jnp.sum(new, dtype=jnp.int32)
    seen_count2 = seen_count + new_count

    B = _merge_block_rows(SC)
    P = -(-SC // B) * B
    QB = _probe_block_rows(N)
    npos = jnp.cumsum(new.astype(jnp.int32)) - 1
    pos_n = lb + npos
    nk_tgt = jnp.where(new, npos, N + sidx)
    src_tgt = jnp.where(new & (pos_n < SC), pos_n, P + sidx)

    def index_block(b, out):
        nk_sidx, src = out
        at = jnp.minimum(b * QB, N - QB)
        rows = at + jnp.arange(QB, dtype=jnp.int32)
        nk_sidx = nk_sidx.at[lax.dynamic_slice(nk_tgt, (at,), (QB,))] \
            .set(lax.dynamic_slice(sidx_s, (at,), (QB,)), mode="drop",
                 unique_indices=True)
        src = src.at[lax.dynamic_slice(src_tgt, (at,), (QB,))] \
            .set(rows, mode="drop", unique_indices=True)
        return nk_sidx, src

    zero = n_live - n_live
    nk_sidx, src = lax.fori_loop(
        0, _probe_blocks(n_live, N), index_block,
        (jnp.zeros((N,), jnp.int32) + zero,
         jnp.full((P,), -1, jnp.int32) + zero))
    c = jnp.cumsum((src >= 0).astype(jnp.int32))

    tail = jnp.concatenate([jnp.ones((1, 1), jnp.int32),
                            jnp.full((1, K - 1), SENTINEL, jnp.int32)],
                           axis=1)
    merge_blocks = _merge_blocks(seen_count2, SC)

    def block(i, table):
        p0 = (merge_blocks - 1 - i) * B
        src_b = lax.dynamic_slice(src, (p0,), (B,))
        is_new = src_b >= 0
        src_s = p0 + jnp.arange(B, dtype=jnp.int32) \
            - lax.dynamic_slice(c, (p0,), (B,))
        at = src_s[0] + is_new[0]
        window = lax.dynamic_slice(table, (at, 0), (B, K))
        from_seen = jnp.take(window, jnp.clip(src_s - at, 0, B - 1),
                             axis=0)
        from_new = jnp.take(skeys, jnp.clip(src_b, 0, N - 1), axis=0)
        is_seen = (src_s < seen_count)[:, None]
        rows = jnp.where(is_new[:, None], from_new,
                         jnp.where(is_seen, from_seen, tail))
        return lax.dynamic_update_slice(table, rows, (p0, 0))

    if P != SC:
        seen = jnp.concatenate([seen, jnp.broadcast_to(tail, (P - SC, K))])
    seen2 = lax.fori_loop(0, merge_blocks, block, seen)[:SC]
    return dict(new_count=new_count, nk_sidx=nk_sidx, seen2=seen2,
                seen_count2=seen_count2,
                probe_blocks=_probe_blocks(n_live, N),
                window_blocks=window_blocks,
                merge_blocks=merge_blocks, sort_slots=sort_slots)


@pytest.fixture
def _window_build(monkeypatch):
    """Call it to lower the floor: what is traced from then on builds
    from windows of the new keys."""
    def lower_the_floor():
        monkeypatch.setattr(bfs, "_BUILD_WHOLE_KEYS", 0)
        assert _build_form(N) == _build_form(1) == "window"
    assert _build_form(N) == _build_form(1 << 21) == "whole"
    assert _build_form((1 << 21) + 1) == "window"
    return lower_the_floor


KEYED = ANSWER + ("probe_blocks", "merge_blocks", "sort_slots")
# one jit a block size, as _FNS: each is first traced with the floor at 0
_KEYED_FNS = {name: jax.jit(functools.partial(_rank_merge),
                            static_argnums=(3, 4, 5, 6)) for name in BLOCKS}


def _check_keyed(got, want, ref, tag):
    """The window form's answers: the whole form's bit for bit (the
    work counters too), numpy's, and the blocks the compaction ran."""
    for name in KEYED:
        assert np.array_equal(np.asarray(got[name]),
                              np.asarray(want[name])), (name, "whole", tag)
    for name in ANSWER:
        assert np.array_equal(np.asarray(got[name]), ref[name]), \
            (name, "numpy", tag)
    assert want.get("newkey_blocks") is None
    assert int(got["newkey_blocks"]) == -(-ref["new_count"] // PROBE_MIN)


@pytest.mark.parametrize("blocks", BLOCKS)
@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("multikey", [False, True])
@pytest.mark.parametrize("K", [3, 5])
def test_windowed_build_equals_the_whole_build_and_set_union(
        K, multikey, scenario, blocks, monkeypatch, _window_build):
    monkeypatch.setattr(bfs, "_MERGE_BLOCK_ROWS", BLOCKS[blocks])
    cases = [_case(scenario, K, np.random.default_rng(
        [K, int(multikey), SCENARIOS.index(scenario), trial, 48]))
        for trial in range(4)]

    def answers(fn):
        return [fn(jnp.asarray(seen), jnp.int32(n_seen), jnp.asarray(keys),
                   N, SC, K, multikey) for seen, n_seen, keys in cases]

    wants = answers(_FNS[blocks])
    _window_build()
    for trial, (got, want) in enumerate(zip(answers(_KEYED_FNS[blocks]),
                                            wants)):
        seen, n_seen, keys = cases[trial]
        _check_keyed(got, want, _np_reference(seen, n_seen, keys, SC, K),
                     trial)
        if scenario == "overflow":
            assert int(got["seen_count2"]) > SC


def _border(name, K, rng):
    """(seen table, seen count, keys, rows of a block of seen2) at a
    border of the window of new keys."""
    uni = _lexsorted(rng.integers(-99, 100, size=(8 * SC, K - 1))
                     .astype(np.int32))
    block, valid = 16, np.ones(N, bool)
    if name == "no_new_key":
        swords, kwords = uni[:SC // 2], uni[rng.integers(0, SC // 2, N)]
    elif name == "every_key_new":
        swords, kwords = uni[:0], uni[rng.permutation(N)]
    elif name == "one_key_many_times":
        swords, kwords = uni[::2][:20], np.repeat(uni[7:8], N, axis=0)
    elif name == "parked_past_SC":
        # 56 seen rows and 48 new keys among and after them: the last
        # new keys take positions past the table's 64
        swords = uni[0:2 * 56:2]
        kwords = uni[1:2 * 56:2][-N:][rng.permutation(N)]
    elif name == "P_over_SC":
        # blocks of 24 rows: P = 72 rows of src and c for SC = 64
        block = 24
        swords, kwords = uni[0:40:2], uni[1:2 * N:2][rng.permutation(N)]
    elif name == "clamped_slice":
        # ten seen rows below 48 new keys: the block at row 48 has 38
        # new keys below it, and N - B = 32 is where its slice starts
        swords, kwords = uni[:10], uni[10:10 + N][rng.permutation(N)]
    else:
        # new_count an exact multiple of QB = 16: the compaction's last
        # block is full
        n_new = {"one_full_block": 16, "two_full_blocks": 32}[name]
        swords = uni[:20]
        kwords = np.concatenate([uni[20:20 + n_new],
                                 uni[rng.integers(0, 20, N - n_new)]])
        # four of the seen keys' slots hold no key at all
        mix = rng.permutation(N)
        kwords, valid = kwords[mix], mix < N - 4
    return _table(swords, SC, K), len(swords), _keys(kwords, valid, K), block


BORDERS = ("no_new_key", "every_key_new", "one_key_many_times",
           "parked_past_SC", "P_over_SC", "clamped_slice",
           "one_full_block", "two_full_blocks")


@pytest.mark.parametrize("border", BORDERS)
@pytest.mark.parametrize("K", [3, 5])
def test_windowed_build_at_the_windows_borders(K, border, monkeypatch,
                                               _window_build):
    rng = np.random.default_rng([K, BORDERS.index(border), 4801])
    seen, n_seen, keys, block = _border(border, K, rng)
    monkeypatch.setattr(bfs, "_MERGE_BLOCK_ROWS", block)
    args = (jnp.asarray(seen), jnp.int32(n_seen), jnp.asarray(keys),
            N, SC, K)
    # jits of this test's own (jit caches by the function, hence the
    # partials): the block size is read when they trace
    want = jax.jit(functools.partial(_whole_rank_merge),
                   static_argnums=(3, 4, 5))(*args)
    _window_build()
    got = jax.jit(functools.partial(_rank_merge),
                  static_argnums=(3, 4, 5))(*args)
    ref = _np_reference(seen, n_seen, keys, SC, K)
    _check_keyed(got, want, ref, border)
    new, blocks = ref["new_count"], int(got["newkey_blocks"])
    assert (new, blocks) == {
        "no_new_key": (0, 0), "every_key_new": (N, 3),
        "one_key_many_times": (1, 1), "parked_past_SC": (N, 3),
        "P_over_SC": (N, 3), "clamped_slice": (N, 3),
        "one_full_block": (16, 1), "two_full_blocks": (32, 2)}[border]
    if border == "parked_past_SC":
        assert ref["seen_count2"] > SC
        # new keys fell off the table, and seen rows with them
        table = {tuple(r) for r in ref["seen2"][:, 1:]}
        fresh = [tuple(keys[i, 1:]) for i in ref["nk_sidx"][:new]]
        assert 0 < sum(w in table for w in fresh) < new
        assert any(tuple(r) not in table for r in seen[:n_seen, 1:])
    if border == "clamped_slice":
        # rows 48..57 are the 39th..48th new keys
        assert np.array_equal(ref["seen2"][48:58, 1:],
                              _lexsorted(keys[:, 1:])[38:])


@pytest.mark.parametrize("landing", LANDINGS)
def test_windowed_build_inside_a_while_loop(landing, _toy_merge_blocks,
                                            _window_build):
    """The resident engine's form, the compaction's blocks counted in
    the carry as the program counts them."""
    K = 5
    _window_build()
    rng = np.random.default_rng([list(LANDINGS).index(landing), 4802])
    table, count, levels = _levels(landing, K, rng)

    @jax.jit
    def run(table, count, keys3):
        def body(carry):
            lvl, table, count, blocks = carry
            rm = _rank_merge(table, count, keys3[lvl], N, SC, K)
            return (lvl + 1, rm["seen2"], rm["seen_count2"],
                    blocks.at[lvl].set(rm["newkey_blocks"]))
        return lax.while_loop(lambda c: c[0] < 3, body,
                              (jnp.int32(0), table, count,
                               jnp.zeros((3,), jnp.int32)))[1:]

    seen2, count2, blocks = run(jnp.asarray(table), jnp.int32(count),
                                jnp.asarray(np.stack(levels)))
    want_blocks = []
    for keys in levels:
        want = _np_live_merge(table, count, keys, K)
        table, count = want["seen2"], want["seen_count2"]
        want_blocks.append(-(-want["new_count"] // PROBE_MIN))
    assert np.array_equal(np.asarray(seen2), table)
    assert int(count2) == count
    assert list(np.asarray(blocks)) == want_blocks


@pytest.mark.parametrize("shift", [0, 1])
def test_windowed_build_under_shard_map(shift, _toy_merge_blocks,
                                        _window_build):
    """A mesh shard's form: each shard compacts its own new keys, its
    loop bounded on its own count (a device-varying carry)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    D, K = 4, 5
    if len(jax.devices()) < D:
        pytest.skip("needs four (virtual) devices")
    _window_build()
    mesh = Mesh(np.array(jax.devices()[:D]), ("d",))
    names = ANSWER + ("merge_blocks", "newkey_blocks")

    def shard(table, count, keys):
        rm = _rank_merge(table[0], count[0], keys[0], N, SC, K, True)
        return tuple(rm[name][None] for name in names)

    step = jax.jit(shard_map(shard, mesh=mesh, in_specs=(P("d"),) * 3,
                             out_specs=(P("d"),) * len(names)))
    lands = [list(LANDINGS)[(d + shift) % len(LANDINGS)] for d in range(D)]
    cases = [_levels(nm, K, np.random.default_rng([d, shift, 4803]))
             for d, nm in enumerate(lands)]
    tables = [c[0] for c in cases]
    counts = [c[1] for c in cases]
    for lvl in range(3):
        keys = [c[2][lvl] for c in cases]
        got = step(jnp.asarray(np.stack(tables)),
                   jnp.asarray(counts, jnp.int32),
                   jnp.asarray(np.stack(keys)))
        for d in range(D):
            got_d = {name: np.asarray(g)[d] for name, g in zip(names, got)}
            want = _check_level(got_d, tables[d], counts[d], keys[d], K,
                                (lands[d], lvl))
            assert int(got_d["newkey_blocks"]) == \
                -(-want["new_count"] // PROBE_MIN)
            tables[d], counts[d] = want["seen2"], want["seen_count2"]


def _lowered_merge(fn, n, sc, K=5, multikey=False, prefix=False):
    """The text of one merge of n key slots into a table of sc, lowered
    on shapes alone: (seen, seen_count, keys[, n_prefix])."""
    def merge(seen, count, keys, *n_prefix):
        rm = fn(seen, count, keys, n, sc, K, multikey, *n_prefix)
        return tuple(v for v in rm.values() if v is not None)
    return jax.jit(merge).lower(
        jax.ShapeDtypeStruct((sc, K), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((n, K), jnp.int32),
        *[jax.ShapeDtypeStruct((), jnp.int32)] * prefix).as_text()


@pytest.mark.parametrize("multikey,prefix", [(False, False), (False, True),
                                             (True, False)],
                         ids=["lsd", "ladder", "multikey"])
def test_no_more_key_slots_than_the_floor_lowers_to_the_form_it_had(
        multikey, prefix, monkeypatch):
    """_build_form(N) == "whole": no compaction of the new keys and the
    text of the function up to PR 47 — the level engine's step, the
    mesh's shards and the small resident programs keep their programs;
    over the floor the text has one loop more."""
    for floor in (N, 2 * N):
        monkeypatch.setattr(bfs, "_BUILD_WHOLE_KEYS", floor)
        text = _lowered_merge(_rank_merge, N, SC, 5, multikey, prefix)
        assert text == _lowered_merge(_whole_rank_merge, N, SC, 5, multikey,
                                      prefix)
    monkeypatch.setattr(bfs, "_BUILD_WHOLE_KEYS", N - 1)
    keyed = _lowered_merge(_rank_merge, N, SC, 5, multikey, prefix)
    assert keyed.count("stablehlo.while") == \
        text.count("stablehlo.while") + 1
    # ... and no scatter more: the compaction is a gather
    for operand, indices, updates in _scatters(_rank_merge, 5, multikey):
        assert (indices, updates) == (f"{QB}x1xi32", f"{QB}xi32")
