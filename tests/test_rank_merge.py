"""Direct contract of backend/bfs._rank_merge (ISSUE 25).

The function places rows by gather through inverse indices built from
scalar scatters.  The row-scatter formulation it replaced lives on here
alone, as the bit-for-bit oracle, beside a numpy sorted-set-union
reference that shares no code with either; a lowering guard keeps row
scatters (39-117 ns a row on the TPU v5e against 4.3 ns for a gathered
row; ledger, PR 24) from coming back."""

import functools
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from jaxmc.backend import bfs  # noqa: E402
from jaxmc.backend.bfs import (  # noqa: E402
    SENTINEL, _lsd_sort, _rank_merge, _seen_probe)


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
        for name in ("new_count", "nk_sidx", "seen2", "seen_count2"):
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
