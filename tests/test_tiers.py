r"""Out-of-core hierarchical seen set (ISSUE 12): device -> host -> disk
tiered rank-merge + fingerprint-only mode.

Pins, on repo-local models only (no reference corpus needed):
  * backend/tiers.py unit contract: `_np_rank_merge` is a set-union of
    sorted runs (vs a tuple-set oracle, negative words included),
    `_keyview` maps signed row order onto unsigned byte order, spill /
    host-compaction / disk-flush / LSM disk compaction preserve exact
    membership, and `dump`/`load` round-trips the whole hierarchy;
  * a failed disk write (the `tier_io_error` fault site, or ENOSPC)
    DEGRADES the store to host-tier-only with the named
    `tier.io_degraded` event — counts stay exact, nothing crashes;
    an unreadable run mid-search (wrong counts, not a degraded mode)
    raises instead;
  * the capped engine run on specs/ooc_scaled.tla (device seen table
    forced to ~17% of the state count, host budget forcing the disk
    tier) completes EXHAUSTIVELY with counts bit-identical to the
    manifest pins, on the single-chip level mode AND the mesh-resident
    loop (per-shard tiering, D=2);
  * truncation results name the exhausted resource (trunc_reason) on
    the serial and device engines;
  * --seen fingerprint parity against the manifest pins on EVERY
    repo-local rung (bench-scale rungs marked slow), with the
    collision-probability bound reported in the result; --seen exact
    refuses modes that cannot honor it;
  * chaos (mid-spill robustness, `-m chaos`): SIGKILL + resume and a
    SIGTERM drain + resume both land bit-identical to the clean capped
    run — the checkpoint carries the full tier hierarchy.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from jaxmc import faults, obs
from jaxmc.backend.tiers import (TieredSeen, _from_keybytes, _held,
                                 _keyview, _lead_column, _np_rank_merge,
                                 _to_keybytes)
from jaxmc.front.cfg import ModelConfig, parse_cfg
from jaxmc.sem.modules import Loader, bind_model

SPECS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "specs")
REPO = os.path.dirname(SPECS)

#: the ooc_scaled fixture's manifest pins (jaxmc/corpus.py)
OOC_WANT = (12289, 3072)
#: ~17% of the rung's 3072 states — the acceptance cap (<= 25%)
OOC_CAP = 512
#: host-tier key budget small enough that the capped run hits disk
OOC_HOST_KEYS = 1024


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    # per-test capacity-profile store + no ambient tier/fault knobs
    monkeypatch.setenv("JAXMC_PROFILE_STORE", str(tmp_path / "prof"))
    for k in ("JAXMC_SEEN_CAP", "JAXMC_TIER_HOST_KEYS",
              "JAXMC_SPILL_DIR", "JAXMC_FAULTS", "JAXMC_FAULTS_STATE"):
        monkeypatch.delenv(k, raising=False)
    faults._CACHE = None
    yield
    faults._CACHE = None


def load(name, cfg_name=None, no_deadlock=False):
    m = Loader([SPECS]).load_path(os.path.join(SPECS, name + ".tla"))
    cfgp = os.path.join(SPECS, (cfg_name or name) + ".cfg")
    if os.path.exists(cfgp):
        cfg = parse_cfg(open(cfgp).read())
    else:
        cfg = ModelConfig(specification="Spec")
    if no_deadlock:
        cfg.check_deadlock = False
    return bind_model(m, cfg)


def _sorted_rows(rows):
    a = np.asarray(rows, np.int32)
    return a[np.argsort(_keyview(a))]


def _rand_runs(rng, n_a, n_b, kd=3, lo=-(1 << 30), hi=1 << 30):
    a = np.unique(rng.integers(lo, hi, (n_a, kd), dtype=np.int64)
                  .astype(np.int32), axis=0)
    b = np.unique(rng.integers(lo, hi, (n_b, kd), dtype=np.int64)
                  .astype(np.int32), axis=0)
    # force overlap so the dedup path is exercised
    if len(a) and len(b):
        k = min(len(a), len(b) // 3)
        b[:k] = a[:k]
    return _sorted_rows(a), _sorted_rows(np.unique(b, axis=0))


# ------------------------------------------------ numpy merge primitives

class TestRankMergePrimitives:
    def test_keyview_orders_signed_rows(self):
        rng = np.random.default_rng(7)
        rows = rng.integers(-(1 << 31), 1 << 31, (500, 4),
                            dtype=np.int64).astype(np.int32)
        rows[:4] = [[-(1 << 31), 0, 0, 0], [(1 << 31) - 1, 0, 0, 0],
                    [0, -1, 5, 5], [0, 1, -5, -5]]
        got = np.argsort(_keyview(rows), kind="stable")
        want = np.lexsort(rows[:, ::-1].T)  # signed lexicographic
        assert np.array_equal(rows[got], rows[want])

    def test_rank_merge_is_sorted_set_union(self):
        rng = np.random.default_rng(11)
        for n_a, n_b in ((0, 9), (9, 0), (1, 1), (64, 17), (33, 400)):
            a, b = _rand_runs(rng, n_a, n_b)
            m = _np_rank_merge(a, b)
            want = {tuple(r) for r in a} | {tuple(r) for r in b}
            assert {tuple(r) for r in m} == want
            assert len(m) == len(want), "merged run kept a duplicate"
            assert np.array_equal(m, _sorted_rows(m)), "merge unsorted"

    def test_rank_merge_idempotent(self):
        rng = np.random.default_rng(3)
        a, _ = _rand_runs(rng, 80, 0)
        assert np.array_equal(_np_rank_merge(a, a), a)


# ------------------------------------------------ TieredSeen unit layer

class TestTieredSeen:
    KD = 3

    def _store(self, tmp_path, budget=10 ** 9):
        return TieredSeen(self.KD, host_budget_keys=budget,
                          spill_dir=str(tmp_path / "spill"))

    def test_spill_probe_membership(self, tmp_path):
        rng = np.random.default_rng(5)
        a, b = _rand_runs(rng, 200, 150, kd=self.KD)
        t = self._store(tmp_path)
        assert not t.active and len(t) == 0
        t.spill(a)
        t.spill(b)
        assert t.active
        inside = np.vstack([a[::7], b[::5]])
        outside = _sorted_rows(rng.integers(1 << 30, (1 << 31) - 1,
                                            (40, self.KD),
                                            dtype=np.int64)
                               .astype(np.int32))
        hits = t.probe(np.vstack([inside, outside]))
        assert hits[: len(inside)].all()
        assert not hits[len(inside):].any()
        assert t.probe(np.zeros((0, self.KD), np.int32)).shape == (0,)

    def test_host_compaction_fan_in(self, tmp_path):
        rng = np.random.default_rng(9)
        t = self._store(tmp_path)
        all_rows = []
        for _ in range(TieredSeen.MAX_HOST_RUNS + 1):
            r, _ = _rand_runs(rng, 60, 0, kd=self.KD)
            t.spill(r)
            all_rows.append(r)
        assert len(t.host_runs) == 1, "fan-in must compact to one run"
        assert t.compactions >= 1
        every = np.unique(np.vstack(all_rows), axis=0)
        assert t.probe(every).all()
        assert len(t) == len(every)

    def test_disk_flush_and_lsm_compaction(self, tmp_path):
        rng = np.random.default_rng(13)
        t = self._store(tmp_path, budget=64)
        all_rows = []
        for _ in range(TieredSeen.MAX_DISK_RUNS + 2):
            r, _ = _rand_runs(rng, 80, 0, kd=self.KD)
            t.spill(r)  # each spill overflows the 64-key host budget
            all_rows.append(r)
        assert t.disk_keys > 0
        assert len(t.disk_runs) <= TieredSeen.MAX_DISK_RUNS, \
            "disk fan-in never compacted"
        for p in t.disk_runs:
            assert os.path.exists(p) and p.endswith(".npy")
        leftover = [f for f in os.listdir(t.spill_dir)
                    if f.endswith(".npy")]
        assert sorted(leftover) == sorted(
            os.path.basename(p) for p in t.disk_runs), \
            "compaction left dead run files behind"
        every = np.unique(np.vstack(all_rows), axis=0)
        assert t.probe(every).all()
        assert len(t) == len(every)
        assert t.stats()["probe_wall_s"] >= 0

    def test_dump_load_roundtrip(self, tmp_path):
        rng = np.random.default_rng(17)
        t = self._store(tmp_path, budget=64)
        rows = []
        for _ in range(3):
            r, _ = _rand_runs(rng, 70, 0, kd=self.KD)
            t.spill(r)
            rows.append(r)
        assert t.disk_keys > 0 and t.host_keys >= 0
        payload = t.dump()
        t2 = TieredSeen(self.KD, host_budget_keys=64,
                        spill_dir=str(tmp_path / "other"))
        t2.load(payload)
        every = np.unique(np.vstack(rows), axis=0)
        assert t2.probe(every).all()
        assert len(t2) == len(t)
        t3 = TieredSeen(self.KD + 1)
        with pytest.raises(ValueError, match="key_words"):
            t3.load(payload)

    def test_ckpt_path_mode_past_inline_budget(self, tmp_path,
                                               monkeypatch):
        # a disk tier past JAXMC_TIER_CKPT_INLINE_KEYS rides the
        # checkpoint as run-file PATHS (O(host) payload); load
        # re-opens and validates them, and a vanished spill dir is a
        # NAMED error, not a silent wrong count
        monkeypatch.setenv("JAXMC_TIER_CKPT_INLINE_KEYS", "1")
        rng = np.random.default_rng(29)
        t = self._store(tmp_path, budget=32)
        rows = []
        for _ in range(3):
            r, _ = _rand_runs(rng, 60, 0, kd=self.KD)
            t.spill(r)
            rows.append(r)
        assert t.disk_keys > 1
        payload = t.dump()
        assert "disk_paths" in payload and "disk" not in payload
        t2 = TieredSeen(self.KD, host_budget_keys=32)
        t2.load(payload)
        every = np.unique(np.vstack(rows), axis=0)
        assert t2.probe(every).all()
        assert len(t2) == len(t)
        for p in payload["disk_paths"]:
            os.unlink(p)
        t3 = TieredSeen(self.KD, host_budget_keys=32)
        with pytest.raises(ValueError, match="spill directory"):
            t3.load(payload)

    def test_compaction_preserves_ckpt_referenced_runs(self, tmp_path,
                                                       monkeypatch):
        # a path-mode checkpoint must survive later LSM compactions:
        # referenced run files are retired, not unlinked, until a
        # newer dump supersedes them
        monkeypatch.setenv("JAXMC_TIER_CKPT_INLINE_KEYS", "1")
        rng = np.random.default_rng(31)
        t = self._store(tmp_path, budget=32)
        early = []
        for _ in range(3):
            r, _ = _rand_runs(rng, 60, 0, kd=self.KD)
            t.spill(r)
            early.append(r)
        p1 = t.dump()
        assert "disk_paths" in p1
        late = []
        for _ in range(TieredSeen.MAX_DISK_RUNS):
            r, _ = _rand_runs(rng, 60, 0, kd=self.KD)
            t.spill(r)
            late.append(r)
        assert t.compactions >= 1
        for p in p1["disk_paths"]:
            assert os.path.exists(p), \
                "compaction unlinked a checkpoint's only copy"
        t_old = TieredSeen(self.KD, host_budget_keys=32)
        t_old.load(p1)
        assert t_old.probe(np.unique(np.vstack(early), axis=0)).all()
        # live store still answers for everything
        every = np.unique(np.vstack(early + late), axis=0)
        assert t.probe(every).all() and len(t) == len(every)
        # a newer dump supersedes the old references: retired files go
        p2 = t.dump()
        gone = [p for p in p1["disk_paths"]
                if p not in p2.get("disk_paths", [])]
        assert gone and all(not os.path.exists(p) for p in gone)

    def test_reset_retires_ckpt_referenced_runs(self, tmp_path,
                                                monkeypatch):
        # cold tiers last ONE search (ISSUE 32): reset() forgets every
        # run, but the run files a path-mode checkpoint references are
        # its only copy — they stay on disk, RETIRED, until the next
        # search's dump() supersedes the reference; then they go.  One
        # set of run files per re-explored search must not pile up
        monkeypatch.setenv("JAXMC_TIER_CKPT_INLINE_KEYS", "0")
        rng = np.random.default_rng(37)
        t = self._store(tmp_path, budget=32)
        spill = str(tmp_path / "spill")
        listings = []
        for search in range(3):
            rows = []
            for _ in range(3):
                r, _ = _rand_runs(rng, 60, 0, kd=self.KD)
                t.spill(r)
                rows.append(r)
            payload = t.dump()
            assert sorted(payload["disk_paths"]) == sorted(
                os.path.join(spill, f) for f in os.listdir(spill))
            listings.append(sorted(os.listdir(spill)))
            t.reset()
            assert not t.active and len(t) == 0 and t.spills == 0
            # the checkpoint of the search that just ended still resumes
            t_ck = TieredSeen(self.KD, host_budget_keys=32)
            t_ck.load(payload)
            assert t_ck.probe(np.unique(np.vstack(rows), axis=0)).all()
        assert len({len(ls) for ls in listings}) == 1, listings
        assert not set(listings[0]) & set(listings[2])
        # nothing referenced any more: a reset leaves the directory empty
        t._ckpt_refs = set()
        t.reset()
        assert os.listdir(spill) == []

    def test_reset_tries_the_disk_again(self, tmp_path, monkeypatch):
        # io_degraded is state of one search too: the failed search's
        # files are gone after reset(), so the next search flushes again
        monkeypatch.setenv("JAXMC_FAULTS", "tier_io_error:op=write")
        faults._CACHE = None
        rng = np.random.default_rng(41)
        t = self._store(tmp_path, budget=32)
        r, _ = _rand_runs(rng, 60, 0, kd=self.KD)
        t.spill(r)
        assert t.io_degraded and t.disk_keys == 0
        monkeypatch.delenv("JAXMC_FAULTS")
        faults._CACHE = None
        t.reset()
        assert t.io_degraded is None and "io_degraded" not in t.stats()
        t.spill(r)
        assert t.io_degraded is None and t.disk_keys == len(r)

    def test_spill_shape_mismatch_rejected(self, tmp_path):
        t = self._store(tmp_path)
        with pytest.raises(ValueError, match="key_words"):
            t.spill(np.zeros((4, self.KD + 2), np.int32))

    def test_io_error_degrades_to_host_only(self, tmp_path,
                                            monkeypatch):
        # the tier_io_error fault site: a failed disk write must leave
        # a host-tier-only store with exact membership and the named
        # event — never a crash
        monkeypatch.setenv("JAXMC_FAULTS", "tier_io_error:op=write")
        faults._CACHE = None
        rng = np.random.default_rng(19)
        tel = obs.Telemetry()
        with obs.use_local(tel):
            t = self._store(tmp_path, budget=32)
            rows = []
            for _ in range(3):
                r, _ = _rand_runs(rng, 50, 0, kd=self.KD)
                t.spill(r)  # overflows the budget -> flush -> fault
                rows.append(r)
        assert t.io_degraded and "tier_io_error" in t.io_degraded
        assert t.disk_keys == 0 and not t.disk_runs
        assert "io_degraded" in t.stats()
        assert "tier.io_degraded" in tel.gauges
        every = np.unique(np.vstack(rows), axis=0)
        assert t.probe(every).all(), "degraded store lost keys"
        assert len(t) == len(every)

    def test_unreadable_disk_run_raises(self, tmp_path):
        rng = np.random.default_rng(23)
        t = self._store(tmp_path, budget=32)
        r, _ = _rand_runs(rng, 60, 0, kd=self.KD)
        t.spill(r)
        assert t.disk_runs
        os.unlink(t.disk_runs[0])
        with pytest.raises(RuntimeError, match="unreadable"):
            t.probe(r[:5])


# ------------------------------------------------ the ordered probe

#: words a fingerprint never avoids: the signed order's two ends, the
#: sign change, and a few small ones so that leading words COLLIDE
_EDGE = np.array([-(1 << 31), (1 << 31) - 1, -1, 0, 1, -2, 2], np.int64)
_LAYOUTS = ("host", "compacted", "flushed", "mixed", "reloaded")
_RUN_KEYS = 90


def _collide_rows(rng, n, kd):
    """Unique sorted rows that COLLIDE in front: the words before the
    last come from _EDGE in the two leading places (the last word is
    small), so many rows share their leading 8 bytes and differ behind
    them, and a made-up query often meets a row."""
    a = rng.integers(-(1 << 31), 1 << 31, (n, kd), dtype=np.int64)
    lead = min(kd - 1, 2)
    a[:, :lead] = _EDGE[rng.integers(0, len(_EDGE), (n, lead))]
    a[:, -1] = rng.integers(-500, 500, n)
    return _sorted_rows(np.unique(a.astype(np.int32), axis=0))


def _spread_rows(rng, n, kd):
    """Unique sorted rows of words drawn from all of int32, as
    fingerprints are: a fence's buckets hold a key or two."""
    a = rng.integers(-(1 << 31), 1 << 31, (n, kd), dtype=np.int64)
    return _sorted_rows(np.unique(a.astype(np.int32), axis=0))


_ROWS = {"collide": _collide_rows, "spread": _spread_rows}


def _laid_out(tmp_path, kd, layout, runs):
    """A store that holds `runs` as the layout says: three host runs;
    one compacted host run; disk runs alone; a disk run and a host run;
    the mixed store dumped and loaded into one with room, where every
    run is a host run again and its fence made anew."""
    budget = {"host": 10 ** 9, "compacted": 10 ** 9, "flushed": 1,
              "mixed": _RUN_KEYS + _RUN_KEYS // 2,
              "reloaded": _RUN_KEYS + _RUN_KEYS // 2}[layout]
    t = TieredSeen(kd, host_budget_keys=budget,
                   spill_dir=str(tmp_path / "spill"))
    for r in runs:
        t.spill(r)
    if layout == "compacted":
        assert len(t.host_runs) == 1 and t.compactions >= 1
    if layout == "reloaded":
        payload = t.dump()
        t = TieredSeen(kd, spill_dir=str(tmp_path / "again"))
        t.load(payload)
    assert (len(t.host_runs), len(t.disk_runs)) == {
        "host": (3, 0), "compacted": (1, 0), "flushed": (0, 3),
        "mixed": (1, 1), "reloaded": (2, 0)}[layout]
    return t


def _runs_for(layout, rng, kd, make=_collide_rows):
    n = TieredSeen.MAX_HOST_RUNS + 1 if layout == "compacted" else 3
    return [make(rng, _RUN_KEYS, kd) for _ in range(n)]


def _expect_verified(t, queries):
    """`keys_verified` from the store's runs as they lie, not from its
    probe: a host run passes on the queries whose leading 8 bytes (4
    where the key is one word) are some row's; a disk run every one."""
    lead = min(t.key_words, 2)
    total = len(queries) * len(t.disk_runs)
    for run in t.host_runs:
        heads = {tuple(r[:lead]) for r in _from_keybytes(run).tolist()}
        total += sum(tuple(q[:lead]) in heads for q in queries.tolist())
    return total


def _probe_and_check(t, queries, oracle):
    before = t.keys_verified
    got = t.probe(queries)
    assert got.dtype == bool and got.shape == (len(queries),)
    assert got.tolist() == [tuple(q) in oracle for q in queries.tolist()]
    rise = t.keys_verified - before
    assert rise == _expect_verified(t, queries)
    assert t.stats()["keys_verified"] == t.keys_verified
    assert int(got.sum()) <= rise <= len(queries) * (
        len(t.host_runs) + len(t.disk_runs))
    return got


class TestOrderedProbe:
    """`TieredSeen.probe` against a plain oracle (a set of row tuples):
    the answer is exact whatever order the queries come in, whatever
    the key's width, and wherever a run lies."""

    @pytest.mark.parametrize("keys", ("spread", "crowded", "both",
                                      "one_key", "twins"))
    @pytest.mark.parametrize("kd", (1, 2, 4))
    def test_the_fence_finds_the_leading_bytes_a_run_holds(self, kd, keys):
        """`_held` against a set of the column's values: keys spread as
        fingerprints are, keys that crowd a few leading values, both in
        one run, a run of one key, rows that share their leading bytes
        (the column holds a value many times: found once)."""
        rng = np.random.default_rng(len(keys) * 10 + kd)
        wide = rng.integers(-(1 << 31), 1 << 31, (300, kd), dtype=np.int64)
        tight = wide.copy()
        tight[:, :2] = rng.integers(0, 40, (300, min(kd, 2)))
        rows = {"spread": wide, "crowded": tight,
                "both": np.vstack([wide[:150], tight[:150]]),
                "one_key": wide[:1],
                "twins": np.repeat(wide[:60], 5, axis=0)}[keys].copy()
        if keys == "twins" and kd > 2:
            rows[:, -1] = np.arange(len(rows))
        run = _to_keybytes(_sorted_rows(np.unique(
            rows.astype(np.int32), axis=0)))
        col = _lead_column(run)
        held = set(col.tolist())
        lead = np.concatenate([
            col[rng.integers(0, len(col), 200)],
            _lead_column(_to_keybytes(np.vstack([wide, tight])
                                      .astype(np.int32))),
            np.array([0, np.iinfo(col.dtype).max], col.dtype)])
        lead = lead[rng.permutation(len(lead))]
        got = _held(col, lead)
        assert sorted(got.tolist()) == [
            i for i, v in enumerate(lead.tolist()) if v in held]
        assert len(_held(col, lead[:0])) == 0

    @pytest.mark.parametrize("order", ("random", "ascending",
                                       "descending"))
    @pytest.mark.parametrize("layout", _LAYOUTS)
    @pytest.mark.parametrize("rows", sorted(_ROWS))
    @pytest.mark.parametrize("kd", (1, 2, 3, 4, 5))
    def test_equals_the_set_oracle(self, tmp_path, kd, rows, layout,
                                   order):
        rng = np.random.default_rng(1000 * kd + len(layout))
        runs = _runs_for(layout, rng, kd, make=_ROWS[rows])
        oracle = {tuple(r) for run in runs for r in run.tolist()}
        t = _laid_out(tmp_path, kd, layout, runs)
        assert len(t) >= len(oracle)   # keys IN RUNS: a twin counts twice
        every = np.vstack(runs)
        inside = every[rng.integers(0, len(every), 120)]
        near = inside.copy()           # same leading bytes, another tail
        near[:, -1] ^= rng.integers(1, 64, len(near), dtype=np.int32)
        q = np.vstack([inside, near, _ROWS[rows](rng, 150, kd),
                       inside[:40]])   # the last block: duplicates
        if order == "random":
            q = q[rng.permutation(len(q))]
        else:
            q = _sorted_rows(q)[::1 if order == "ascending" else -1]
        got = _probe_and_check(t, q, oracle)
        assert got.any() and not got.all()

    @pytest.mark.parametrize("kind", ("empty", "all_hits", "no_hit",
                                      "one_key_many_times"))
    @pytest.mark.parametrize("kd", (1, 2, 3, 4, 5))
    def test_edge_queries(self, tmp_path, kd, kind):
        rng = np.random.default_rng(77 + kd)
        runs = _runs_for("mixed", rng, kd)
        oracle = {tuple(r) for run in runs for r in run.tolist()}
        t = _laid_out(tmp_path, kd, "mixed", runs)
        every = np.vstack(runs)
        if kind == "empty":
            q = np.zeros((0, kd), np.int32)
        elif kind == "all_hits":
            q = every[rng.permutation(len(every))]
        elif kind == "no_hit":
            # the word no row has in its last place
            q = every[rng.permutation(len(every))[:100]].copy()
            q[:, -1] = 10 ** 6
        else:
            q = np.repeat(every[5:6], 64, axis=0)
        got = _probe_and_check(t, q, oracle)
        assert len(got) == len(q)
        if kind != "empty":
            assert got.all() == got.any() == (kind != "no_hit")

    @pytest.mark.parametrize("layout", ("host", "compacted", "mixed"))
    @pytest.mark.parametrize("kd", (3, 4, 5))
    def test_rows_that_all_share_their_leading_bytes(self, tmp_path, kd,
                                                     layout):
        """The fence at its worst: every row of every run, and every
        query, starts with the same 8 bytes, so each query passes each
        fence and the whole-row search decides them all."""
        def same_head(rng, n, kd):
            a = np.empty((n, kd), np.int32)
            a[:, :2] = (-7, 1 << 30)
            a[:, 2:-1] = 3
            a[:, -1] = rng.permutation(10 * n)[:n] - 5 * n
            return _sorted_rows(a)
        rng = np.random.default_rng(31 * kd)
        runs = _runs_for(layout, rng, kd, make=same_head)
        oracle = {tuple(r) for run in runs for r in run.tolist()}
        t = _laid_out(tmp_path, kd, layout, runs)
        q = same_head(rng, 400, kd)[rng.permutation(400)]
        before = t.keys_verified
        got = _probe_and_check(t, q, oracle)
        assert t.keys_verified - before == len(q) * (
            len(t.host_runs) + len(t.disk_runs))
        assert got.any() and not got.all()

    @pytest.mark.parametrize("case", ("stays", "compacted", "flushed",
                                      "loaded"))
    def test_a_fence_is_built_only_for_a_run_that_stays(
            self, tmp_path, monkeypatch, case):
        """The fence comes last in a spill() or a load(), after their
        compactions and flushes: a run that stays keeps the fence it
        has, a spill that compacts builds the merged run's alone, one
        that flushes builds none, a load one a host run it leaves."""
        from jaxmc.backend import tiers
        built = []
        real = tiers._lead_column
        monkeypatch.setattr(
            tiers, "_lead_column",
            lambda kb: built.append(len(kb)) or real(kb))
        rng = np.random.default_rng(7)
        runs = [_rand_runs(rng, 40, 0, kd=4)[0] for _ in range(5)]
        t = TieredSeen(4, host_budget_keys=(
            150 if case in ("flushed", "loaded") else 1 << 20),
            spill_dir=str(tmp_path / "spill"))
        if case == "loaded":
            t.load({"key_words": 4, "host": runs[:2], "spills": 5,
                    "compactions": 0, "disk": runs[2:]})
            # 80 + 40 keys stay under the budget, the fourth run passes
            # it and all go to disk as one; the fifth stays on the host
            assert (len(t.host_runs), len(t.disk_runs)) == (1, 1)
            assert built == [len(runs[4])]
            return
        for run in runs[:3]:
            t.spill(run)
        kept = [fence for _, fence in t._host]
        assert built == [len(r) for r in runs[:3]]
        t.spill(runs[3])
        if case == "flushed":       # 160 keys pass the budget of 150
            assert (t.host_runs, len(t.disk_runs)) == ([], 1)
            assert len(built) == 3
            return
        assert all(a is b for a, b in zip(kept, (f for _, f in t._host)))
        assert len(built) == 4
        if case == "compacted":     # a fifth run: five compact into one
            t.spill(runs[4])
            assert len(t.host_runs) == 1 and t.compactions == 1
            assert built[4:] == [len(t.host_runs[0])]
        assert all(fence is not None for _, fence in t._host)

    def test_the_fence_is_derived_and_the_formats_are_the_old_ones(
            self, tmp_path):
        """A payload as every earlier PR wrote it loads and probes; a
        new dump has the same keys and int32 rows; a run file is the
        keybyte array alone; the fence never leaves the process."""
        rng = np.random.default_rng(50)
        a, b = _rand_runs(rng, 80, 70, kd=4)
        c, _ = _rand_runs(rng, 60, 0, kd=4)
        old = {"key_words": 4, "host": [a], "spills": 3,
               "compactions": 0, "disk": [b, c]}
        t = TieredSeen(4, host_budget_keys=100,
                       spill_dir=str(tmp_path / "spill"))
        t.load(old)   # a and b pass the budget and go to disk; c stays
        assert len(t.disk_runs) == 1 and len(t.host_runs) == 1
        for run, col in t._host:
            assert col.dtype == np.uint64 and col.flags.c_contiguous
            assert np.array_equal(col, _lead_column(run))
            assert np.array_equal(np.sort(col), col)
        oracle = {tuple(r) for r in np.vstack([a, b, c]).tolist()}
        q = np.vstack([a[::3], b[::4], c[::5], a[:20] + 1])
        _probe_and_check(t, q, oracle)
        payload = t.dump()
        assert set(payload) == {"key_words", "host", "spills",
                                "compactions", "disk"}
        for run in payload["host"] + payload["disk"]:
            assert run.dtype == np.int32 and run.shape[1] == 4
        for path in t.disk_runs:
            on_disk = np.load(path)
            assert on_disk.dtype == np.dtype(">u4")
            assert on_disk.ndim == 2 and on_disk.shape[1] == 4
        assert sorted(os.listdir(t.spill_dir)) == sorted(
            os.path.basename(p) for p in t.disk_runs)
        one = TieredSeen(1)
        one.spill(np.array([[-5], [0], [7]], np.int32))
        assert one._host[0][1].dtype == np.uint32
        assert one.probe(np.array([[7], [6], [-5]], np.int32)).tolist() \
            == [True, False, True]


# ------------------------------------------------ capped engine parity

def _capped_kw(tmp_path, cap=OOC_CAP, host=OOC_HOST_KEYS):
    return dict(seen_cap=cap, host_tier_keys=host,
                spill_dir=str(tmp_path / "spill"))


class TestCappedExhaustive:
    def test_level_mode_spills_both_tiers_exact(self, tmp_path):
        # the acceptance run: device table capped at ~17% of the state
        # count, host budget forcing disk — the search must complete
        # exhaustively (no truncation) with the manifest pins
        from jaxmc.backend.bfs import TpuExplorer
        res = TpuExplorer(load("ooc_scaled"),
                          **_capped_kw(tmp_path)).run()
        assert res.ok and not res.truncated
        assert (res.generated, res.distinct) == OOC_WANT
        assert res.seen_mode == "exact"
        assert res.tiers and res.tiers["spills"] > 0
        assert res.tiers["disk_keys"] > 0, "disk tier never exercised"
        assert res.tiers["probe_wall_s"] >= 0

    def test_resident_mode_spills_both_tiers_exact(self, tmp_path):
        # the resident loop's spill path: cap overflow rolls the level
        # back, compacts the sorted prefix out, and redoes the level
        # against an empty table — exhaustive at the manifest pins
        from jaxmc.backend.bfs import TpuExplorer
        res = TpuExplorer(load("ooc_scaled"), resident=True,
                          chunk=256, **_capped_kw(tmp_path)).run()
        assert res.ok and not res.truncated
        assert (res.generated, res.distinct) == OOC_WANT
        assert res.tiers and res.tiers["spills"] > 0
        assert res.tiers["disk_keys"] > 0, "disk tier never exercised"

    def test_mesh_per_shard_tiering_exact(self, tmp_path):
        # per-shard device caps on the mesh-resident loop (D=2):
        # owner-routed keys partition the space, one combined cold
        # store answers membership for every shard
        import jax
        from jax.sharding import Mesh
        from jaxmc.backend.mesh import MeshExplorer
        me = MeshExplorer(load("ooc_scaled"),
                          mesh=Mesh(np.array(jax.devices()[:2]),
                                    ("d",)),
                          **_capped_kw(tmp_path, cap=2 * OOC_CAP))
        res = me.run()  # resident loop: no PROPERTYs/refiners here
        assert res.ok and not res.truncated
        assert (res.generated, res.distinct) == OOC_WANT
        assert res.tiers and res.tiers["spills"] > 0

    def test_capped_counterexample_byte_identical(self, tmp_path):
        # the violation rung (ooc_scaled_bad.cfg, NoMeet): the capped run
        # spills before it reaches the violation, and the counterexample it
        # renders is the uncapped run's, byte for byte (the deleted `make
        # ooc-check` leg 4, ISSUE 43)
        from jaxmc.backend.bfs import TpuExplorer
        from jaxmc.engine.explore import format_trace
        plain = TpuExplorer(load("ooc_scaled", "ooc_scaled_bad")).run()
        capped = TpuExplorer(load("ooc_scaled", "ooc_scaled_bad"),
                             **_capped_kw(tmp_path)).run()
        assert plain.tiers is None
        assert capped.tiers and capped.tiers["spills"] > 0
        for res in (plain, capped):
            assert not res.ok and res.violation.kind == "invariant"
            assert res.violation.name == "NoMeet"
        assert (capped.generated, capped.distinct, capped.diameter) == \
            (plain.generated, plain.distinct, plain.diameter)
        text = format_trace(plain.violation)
        assert text.startswith("Error: Invariant NoMeet is violated.")
        assert len(text.splitlines()) > 20
        assert format_trace(capped.violation) == text

    def test_capped_fingerprint_parity_and_key_words_ratio(self, tmp_path):
        # --seen fingerprint under the same cap (leg 3 of the deleted `make
        # ooc-check`): the manifest pins through both cold tiers, a
        # collision probability in the result, and >= 4x the states a tier
        # row holds (exact key words over fingerprint key words)
        from jaxmc.backend.bfs import TpuExplorer
        fp = TpuExplorer(load("ooc_scaled"), seen_mode="fingerprint",
                         **_capped_kw(tmp_path))
        res = fp.run()
        assert res.ok and not res.truncated
        assert (res.generated, res.distinct) == OOC_WANT
        assert res.seen_mode == "fingerprint"
        assert res.collision_p is not None and 0 < res.collision_p < 1e-20
        assert res.tiers["spills"] > 0 and res.tiers["disk_keys"] > 0
        exact = TpuExplorer(load("ooc_scaled"), seen_mode="exact")
        assert exact.K == exact.PW + 1 and fp.K == 5
        assert exact.K / fp.K >= 4.0
        # ... and the uncapped exact run is what both are held to
        plain = exact.run()
        assert (plain.generated, plain.distinct) == OOC_WANT
        assert plain.seen_mode == "exact" and plain.tiers is None

    def test_engine_io_degrade_keeps_exact_counts(self, tmp_path,
                                                  monkeypatch):
        # end-to-end fault containment: the disk tier dies mid-search,
        # the run degrades to host-tier-only and still lands the pins
        monkeypatch.setenv("JAXMC_FAULTS", "tier_io_error:op=write")
        faults._CACHE = None
        from jaxmc.backend.bfs import TpuExplorer
        res = TpuExplorer(load("ooc_scaled"),
                          **_capped_kw(tmp_path)).run()
        assert res.ok and not res.truncated
        assert (res.generated, res.distinct) == OOC_WANT
        assert res.tiers and res.tiers.get("io_degraded")
        assert res.tiers["disk_keys"] == 0


# ------------------------------------------ cold tiers last ONE search

TRANSFER = os.path.join(REPO, "bench", "specs", "transfer_scaled.tla")
#: 4 procs / MaxMoney 2: 19,101 generated / 7,293 distinct / 13 levels, the
#: cell desk-ooc-4p8's model at a size XLA:CPU answers in seconds
TOY = (4, 2)
#: the resident engine at this cap spills three times and drops 67 cold
#: duplicates (tests/test_bench_pins.py has the arithmetic)
TOY_CAPS = {"SC": 4096, "FCap": 2048, "AccCap": 8192, "VC": 256}
#: per engine: the model's size, the session options and a cap that makes
#: it spill and drop cold duplicates without a breach (the level engine must
#: seat its whole candidate BLOCK, A x FC; the mesh compiles longest, so it
#: gets 3 procs / MaxMoney 3: 4,963 / 2,455)
ENGINES = {
    "level": (TOY, dict(), 1 << 15),
    "resident": (TOY, dict(resident=True, no_trace=True, res_caps=TOY_CAPS,
                           chunk=64), TOY_CAPS["SC"]),
    "mesh": ((3, 3), dict(devices=2, no_trace=True), 1 << 11),
}


def _reference():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "plain_reference", os.path.join(REPO, "bench", "reference",
                                        "transfer_scaled.py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    return reference


def _toy_cfg_text(procs, max_money):
    return ("SPECIFICATION Spec\nINVARIANT AliceBounded\nCONSTANTS\n"
            "  Procs = {%s}\n  MaxMoney = %d\n"
            % (", ".join("p%d" % (i + 1) for i in range(procs)), max_money))


def _toy_session(tmp_path, tel, size=TOY, **kw):
    from jaxmc.session import CheckSession, SessionConfig
    cfg = tmp_path / "toy.cfg"
    cfg.write_text(_toy_cfg_text(*size))
    return CheckSession(SessionConfig(
        spec=TRANSFER, cfg=str(cfg), backend="jax", platform="cpu", **kw),
        tel=tel)


def _answer(res):
    return (res.generated, res.distinct, res.diameter, res.ok,
            res.truncated)


class TestColdTiersLastOneSearch:
    """ISSUE 32: `explore()` is re-runnable on one session (PR 7) and the
    cold tiers (ISSUE 12) outlived the search that filled them: the second
    search of a capped session probed its frontier against the FIRST
    search's states, dropped all of it and answered `ok` with one distinct
    state."""

    @pytest.mark.parametrize("engine", sorted(ENGINES))
    def test_reexplore_on_one_session_keeps_counts(self, engine, tmp_path,
                                                   monkeypatch):
        monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")
        size, opts, cap = ENGINES[engine]
        want = _reference().explore(*size)
        want = (want["generated"], want["distinct"], want["diameter"],
                True, False)
        tel = obs.Telemetry()
        with obs.use(tel):
            uncapped = _toy_session(tmp_path, tel, size, **opts).explore()
            assert _answer(uncapped) == want and uncapped.tiers is None
            sess = _toy_session(tmp_path, tel, size, seen_cap=cap, **opts)
            firsts = None
            for i in range(3):
                before = dict(tel.counters)
                res = sess.explore()
                assert _answer(res) == want, (engine, i)
                assert res.tiers and res.tiers["spills"] > 0
                assert "cap_breached" not in res.tiers
                rise = {k: v - before.get(k, 0)
                        for k, v in tel.counters.items()
                        if k.startswith("tier.")}
                assert rise["tier.spills"] == res.tiers["spills"]
                assert rise["tier.spilled_keys"] == \
                    res.tiers["host_keys"] == \
                    tel.gauges["tier.occupancy"]["host"]
                assert rise["tier.keys_probed"] > \
                    rise["tier.keys_dropped"] > 0
                # what passed a run's fence: every key found did, and
                # no key more than once a run
                assert rise["tier.keys_dropped"] <= \
                    rise["tier.keys_verified"] <= \
                    rise["tier.keys_probed"] * res.tiers["spills"]
                assert res.tiers["keys_verified"] == \
                    rise["tier.keys_verified"]
                # every search does what the first did: same spills, same
                # probes, nothing carried over
                firsts = firsts or rise
                assert rise == firsts, (engine, i)
        assert "tier.cap_breached" not in tel.gauges

    def test_resume_keeps_its_cold_tiers(self, tmp_path, monkeypatch):
        """The checkpoint path is NOT reset: an engine that has searched
        (and spilled) before, resumed from a truncation checkpoint written
        after the first spill, probes the checkpoint's run again."""
        monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")
        from jaxmc.backend.bfs import TpuExplorer
        want = _reference().explore(*TOY)
        mod = Loader([os.path.dirname(TRANSFER)]).load_path(TRANSFER)
        ck = str(tmp_path / "toy.ck")
        ex = TpuExplorer(bind_model(mod, parse_cfg(_toy_cfg_text(*TOY))),
                         resident=True, store_trace=False, chunk=64,
                         res_caps=TOY_CAPS, seen_cap=TOY_CAPS["SC"],
                         max_states=3000, checkpoint_path=ck)
        cut = ex.run()
        # level 4 spilled 1,728 keys and committed 3,180 distinct states
        assert cut.truncated and cut.distinct == 3180
        assert cut.tiers["spills"] == 1 and cut.tiers["host_keys"] == 1728
        ex.max_states, ex.checkpoint_path, ex.resume_from = None, None, ck
        res = ex.run()
        assert _answer(res) == (want["generated"], want["distinct"],
                                want["diameter"], True, False)
        # the checkpoint's run and the two spills after it
        assert res.tiers["spills"] == 3 and res.tiers["host_keys"] == 4679
        ex.resume_from = None
        again = ex.run()
        assert _answer(again) == _answer(res)
        assert again.tiers["spills"] == 3

    def test_soft_breach_is_named(self, tmp_path, monkeypatch):
        """A cap below one level's candidates cannot hold: the table grows
        past it, and says so in a gauge and in `result.tiers` on every
        search, not only in a log line."""
        monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")
        want = _reference().explore(*TOY)
        widest = max(cand for _, cand, _ in want["levels"])
        _, opts, _ = ENGINES["resident"]
        tel = obs.Telemetry()
        with obs.use(tel):
            sess = _toy_session(tmp_path, tel, seen_cap=2048, **dict(
                opts, res_caps=dict(TOY_CAPS, SC=2048)))
            assert widest > 2048
            for _ in range(2):
                res = sess.explore()
                assert (res.generated, res.distinct, res.diameter) == \
                    (want["generated"], want["distinct"], want["diameter"])
                assert res.tiers["cap_breached"] == 8192 >= widest
                assert tel.gauges["tier.cap_breached"] == 8192
                assert tel.gauges["tier.device_cap"] == 2048


    def test_the_capped_tables_are_made_on_the_device(self, tmp_path,
                                                      monkeypatch):
        """`_device_table` is the host-built table, row for row; a
        resident search hands the device its init rows alone, capped or
        not: ONE path since ISSUE 35 (up to PR 34 the uncapped search
        built both tables on the host at full capacity; the tables the
        engines start from: `tests/test_device_seed.py`)."""
        monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")
        from jaxmc.backend.bfs import SENTINEL, TpuExplorer
        head = np.arange(12, dtype=np.int32).reshape(4, 3)
        want = np.full((16, 3), SENTINEL, np.int32)
        want[:4] = head
        assert np.array_equal(TpuExplorer._device_table((16, 3), head), want)
        want[:4] = SENTINEL
        for none in (None, head[:0]):
            assert np.array_equal(
                TpuExplorer._device_table((16, 3), none), want)
        _, opts, cap = ENGINES["resident"]
        seed = {}
        for name, kw in (("uncapped", {}), ("capped", {"seen_cap": cap})):
            tel = obs.Telemetry()
            with obs.use(tel):
                sess = _toy_session(tmp_path, tel, **opts, **kw)
                sess.explore()
                ex = sess.engine
            seed[name] = tel.counters["search.seed_bytes"]
        # 4 procs / MaxMoney 2: 16 init states, their keys and packed rows
        assert seed["uncapped"] == seed["capped"] == 4 * 16 * (ex.K + ex.PW)
        full = 4 * (TOY_CAPS["SC"] * ex.K + TOY_CAPS["FCap"] * ex.PW)
        assert seed["capped"] < full // 16

    def test_reexplore_leaks_no_run_files(self, tmp_path, monkeypatch):
        """A re-explored search whose disk tier is past the checkpoint's
        inline budget leaves the run files of ITS checkpoint in the spill
        directory and no others: the set before it goes when its own
        checkpoint supersedes the reference."""
        monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")
        monkeypatch.setenv("JAXMC_TIER_CKPT_INLINE_KEYS", "0")
        from jaxmc.backend.bfs import TpuExplorer
        ex = TpuExplorer(load("ooc_scaled"), resident=True, chunk=256,
                         checkpoint_path=str(tmp_path / "ooc.ck"),
                         final_checkpoint=True, **_capped_kw(tmp_path))
        spill = str(tmp_path / "spill")
        listings = []
        for _ in range(3):
            res = ex.run()
            assert res.ok and not res.truncated
            assert (res.generated, res.distinct) == OOC_WANT
            assert res.tiers["disk_keys"] > 0
            listings.append(sorted(os.listdir(spill)))
            assert len(listings[-1]) == res.tiers["disk_runs"], listings
        assert not set(listings[0]) & set(listings[1])


class TestTruncationAttribution:
    def test_serial_names_max_states(self):
        from jaxmc.engine.explore import Explorer
        res = Explorer(load("constoy"), max_states=5).run()
        assert res.truncated
        assert res.trunc_reason and \
            res.trunc_reason.startswith("max_states")

    def test_device_names_max_states(self, tmp_path):
        from jaxmc.backend.bfs import TpuExplorer
        res = TpuExplorer(load("ooc_scaled"), max_states=500,
                          **_capped_kw(tmp_path)).run()
        assert res.truncated
        assert res.trunc_reason and \
            res.trunc_reason.startswith("max_states")

    def test_complete_run_carries_no_reason(self):
        from jaxmc.engine.explore import Explorer
        res = Explorer(load("constoy")).run()
        assert not res.truncated and res.trunc_reason is None


# ------------------------------------------------ fingerprint-only mode

def _fp_params():
    from jaxmc.corpus import CASES
    out = []
    for c in CASES:
        if c.root != "repo" or c.jax != "yes" or c.expect != "ok" \
                or c.distinct is None or getattr(c, "lint_only", False):
            continue
        marks = []
        if c.slow or (c.generated or 0) > 20000:
            marks.append(pytest.mark.slow)  # bench-scale rungs
        out.append(pytest.param(
            c, id=os.path.basename(c.cfg or c.spec), marks=marks))
    return out


class TestFingerprintMode:
    @pytest.mark.parametrize("case", _fp_params())
    def test_parity_on_repo_rung(self, case):
        # --seen fingerprint must land the exact manifest pins on
        # every repo-local rung and report its collision bound
        for d in case.include_dirs():
            if not os.path.isdir(d):
                pytest.skip(f"needs the reference corpus ({d})")
        from jaxmc.backend.bfs import TpuExplorer
        from jaxmc.compile.vspec import Bounds
        cfg = parse_cfg(open(case.cfg_path()).read())
        if case.no_deadlock:
            cfg.check_deadlock = False
        spec = case.spec_path()
        model = bind_model(
            Loader([os.path.dirname(spec)]
                   + case.include_dirs()).load_path(spec), cfg)
        b = Bounds()
        for k in ("seq_cap", "grow_cap", "kv_cap"):
            if getattr(case, k, None):
                setattr(b, k, getattr(case, k))
        from jaxmc.compile.vspec import ModeError
        try:
            res = TpuExplorer(model, bounds=b,
                              seen_mode="fingerprint").run()
        except ModeError as ex:
            # hybrid-by-construction rungs run in host_seen mode (the
            # same ladder run_case uses)
            if "hybrid" not in str(ex):
                raise
            from jaxmc import native_store
            if not native_store.is_available():
                pytest.skip("hybrid rung needs the native store")
            res = TpuExplorer(model, bounds=b, host_seen=True,
                              seen_mode="fingerprint").run()
        assert res.ok, res.warnings
        assert (res.generated, res.distinct) == \
            (case.generated, case.distinct)
        assert res.seen_mode == "fingerprint"
        # the bound covers every ADMITTED key (constraint-discarded
        # states hold keys too), so it sits between distinct^2 and
        # (generated + distinct)^2 over 2^129
        assert res.collision_p is not None
        lo = res.distinct ** 2 * 2.0 ** -129
        hi = (res.generated + res.distinct) ** 2 * 2.0 ** -129
        assert lo * 0.999 <= res.collision_p <= hi * 1.001

    def test_exact_refuses_fp_only_modes(self):
        from jaxmc.backend.bfs import TpuExplorer
        from jaxmc.compile.vspec import ModeError
        with pytest.raises(ModeError, match="resident"):
            TpuExplorer(load("constoy"), resident=True,
                        seen_mode="exact")

    def test_exact_refused_on_mesh(self):
        # mesh seen shards are fingerprint-based: --seen exact must
        # refuse, not silently fingerprint past the contract
        from jaxmc.backend.mesh import MeshExplorer
        from jaxmc.compile.vspec import ModeError
        with pytest.raises(ModeError, match="mesh"):
            MeshExplorer(load("constoy"), seen_mode="exact")

    def test_unknown_mode_rejected(self):
        from jaxmc.backend.bfs import TpuExplorer
        from jaxmc.compile.vspec import ModeError
        with pytest.raises(ModeError, match="unknown --seen"):
            TpuExplorer(load("constoy"), seen_mode="sketchy")


# ------------------------------------------------ obs diff attribution

class TestObsDiffIoDegrade:
    def _artifact(self, path, degraded):
        tel = obs.Telemetry()
        tel.level(0, frontier=1, generated=100, wall_s=1.0)
        tel.set_meta(backend="jax", spec="specs/ooc_scaled.tla",
                     env={"jax_version": "0", "platform": "cpu",
                          "device_count": 1})
        if degraded:
            tel.gauge("tier.io_degraded", "tier_io_error: op=write")
        tel.write_metrics(str(path), result={
            "ok": True, "distinct": 50, "generated": 100,
            "diameter": 3, "truncated": False, "wall_s": 1.0})
        return str(path)

    def test_io_degrade_appearance_flagged(self, tmp_path):
        import io as _io
        from jaxmc.obs import report
        good = self._artifact(tmp_path / "a.json", degraded=False)
        bad = self._artifact(tmp_path / "b.json", degraded=True)
        out = _io.StringIO()
        rc = report.main(["diff", good, bad, "--fail-on-regress"],
                         out=out)
        assert rc == 1
        assert "REGRESS tier io degradation" in out.getvalue()
        out = _io.StringIO()
        rc = report.main(["diff", bad, bad, "--fail-on-regress"],
                         out=out)
        assert rc == 0, "a standing degradation must not re-flag"


# ------------------------------------------------ chaos: mid-spill

def _cli(args, env_extra, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "-m", "jaxmc", "check"] + args,
        capture_output=True, text=True, cwd=REPO, env=env,
        timeout=timeout)


def _counts(stdout):
    for line in reversed(stdout.splitlines()):
        if "states generated," in line and "distinct states found" in \
                line and "states/sec" in line:
            parts = line.split()
            return int(parts[0]), int(parts[3])
    raise AssertionError(f"no summary line in:\n{stdout}")


_OOC_ARGS = [os.path.join(SPECS, "ooc_scaled.tla"),
             "--backend", "jax", "--platform", "cpu"]


def _capped_env(tmp_path):
    return {"JAXMC_SEEN_CAP": str(OOC_CAP),
            "JAXMC_TIER_HOST_KEYS": str(OOC_HOST_KEYS),
            "JAXMC_SPILL_DIR": str(tmp_path / "spill"),
            "JAXMC_PROFILE_STORE": str(tmp_path / "prof")}


@pytest.mark.chaos
@pytest.mark.slow
class TestMidSpillChaos:
    """SIGKILL and SIGTERM-drain a capped run AFTER it has spilled,
    then resume: the checkpoint carries the full tier hierarchy, so
    the resumed totals must be bit-identical to the manifest pins."""

    def test_kill_resume_parity_mid_spill(self, tmp_path):
        env = _capped_env(tmp_path)
        ck = str(tmp_path / "ooc.ck")
        killed = _cli(_OOC_ARGS + ["--checkpoint", ck,
                                   "--checkpoint-every", "0"],
                      env_extra=dict(env,
                                     JAXMC_FAULTS="run_kill:level=10"))
        assert killed.returncode in (-9, 137), \
            (killed.returncode, killed.stderr[-500:])
        assert "tier:" in killed.stdout, \
            "the run was killed before any spill — not mid-spill"
        assert os.path.exists(ck), "no checkpoint survived the kill"
        resumed = _cli(_OOC_ARGS + ["--resume", ck], env_extra=env)
        assert resumed.returncode == 0, resumed.stderr[-500:]
        assert _counts(resumed.stdout) == OOC_WANT

    def test_sigterm_drain_resume_parity_mid_spill(self, tmp_path):
        env = _capped_env(tmp_path)
        ck = str(tmp_path / "drain.ck")
        p = subprocess.Popen(
            [sys.executable, "-m", "jaxmc", "check"] + _OOC_ARGS
            + ["--checkpoint", ck, "--checkpoint-every", "0"],
            cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu", **env))
        # the capped search runs ~8s after a ~4s compile; spills start
        # within the first levels — signal mid-search
        time.sleep(6.0)
        p.send_signal(signal.SIGTERM)
        out, err = p.communicate(timeout=120)
        if p.returncode == 0:
            pytest.skip("run finished before the signal landed "
                        "(box too fast for the fixed delay)")
        assert p.returncode == 143, (p.returncode, err[-500:])
        assert "drained" in err
        assert os.path.exists(ck)
        resumed = _cli(_OOC_ARGS + ["--resume", ck], env_extra=env)
        assert resumed.returncode == 0, resumed.stderr[-500:]
        assert _counts(resumed.stdout) == OOC_WANT
        # the drained run must have spilled before the signal, or this
        # proved nothing about mid-spill state
        if "tier:" not in out:
            pytest.skip("drain landed before the first spill")
