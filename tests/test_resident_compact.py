"""The resident level's compaction follows the rows that are new (ISSUE 46).

After the merge `rm["nk_sidx"]` names the level's new rows first, so
`bfs._gather_prefix` gathers them in blocks of RB =
`bfs._compact_block_rows(AccCap, FCap)` bounded on `new_count`; where
the cfg has no CONSTRAINT the blocks write straight into the next
frontier and nothing is sorted, where it has one the kept rows are
compacted from them.  Here the engine at toy size with the block floor
lowered, so that a level takes several blocks: its counts, verdict and
trace are the interpreter's and the plain reference's at the block's
edges (no new row, exactly k blocks, one row more and one fewer), with
a frontier that overflows and grows, under a CONSTRAINT that discards
rows of every level, with traces kept, under `--seen-cap`, with POR and
after a resume; and `search.slots_compacted` is the sum, over the levels
the dispatches really ran, of what the ONE rule (`bfs._compact_blocks`)
gives for each level's new rows."""

import os
import re

import numpy as np
import pytest

pytest.importorskip("jax")

from jaxmc import obs  # noqa: E402
from jaxmc.backend import bfs  # noqa: E402
from jaxmc.session import CheckSession, SessionConfig  # noqa: E402

from test_bench_pins import TRANSFER, _reference, _toy_cfg  # noqa: E402
from test_resident_trace import (  # noqa: E402,F401
    VIOLATION, _cfg, _plain, reference)
from test_sort_ladder import SPECS, _answer, _levels_run  # noqa: E402

RESIDENT = dict(backend="jax", platform="cpu", resident=True, chunk=64)
# 3 procs / MaxMoney 2: ten levels of 24, 48, 116, 177, 160, 116, 59,
# 24, 8 and 0 new rows; the floors below cut them at their edges
CAPS = {"SC": 1 << 11, "FCap": 256, "AccCap": 1 << 10, "VC": 256}

GRID = """---- MODULE grid ----
EXTENDS Naturals
VARIABLES x, y, z
Init == x = 0 /\\ y = 0 /\\ z = 0
Next == \\/ x < 5 /\\ x' = x + 1 /\\ UNCHANGED <<y, z>>
        \\/ y < 5 /\\ y' = y + 1 /\\ UNCHANGED <<x, z>>
        \\/ z < 5 /\\ z' = z + 1 /\\ UNCHANGED <<x, y>>
Spec == Init /\\ [][Next]_<<x, y, z>>
InBox == x + y + z =< 15
Near == x + y =< 4
====
"""


@pytest.fixture(autouse=True)
def _no_capacity_profiles(monkeypatch):
    monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")


def _explore(spec, cfg, floor, monkeypatch, **opts):
    monkeypatch.setattr(bfs, "_PROBE_BLOCK_MIN", floor)
    tel = obs.Telemetry()
    with obs.use(tel):
        sess = CheckSession(SessionConfig(
            spec=spec, cfg=cfg, **dict(RESIDENT, **opts)), tel=tel)
        res = sess.explore()
    return res, tel, sess


def _interp(spec, cfg, **opts):
    return CheckSession(SessionConfig(spec=spec, cfg=cfg, backend="interp",
                                      **opts)).explore()


# ------------------------------------------------------ the kernel alone

@pytest.mark.parametrize("n", [0, 1, 63, 64, 65, 128, 191, 192, 200, 300])
def test_gather_prefix_is_the_whole_take_masked(n):
    """rows[idx[i]] for i < min(n, cap), SENTINEL past — at a block's
    edges, with a last block that overlaps its neighbour (64 does not
    divide 200) and with more rows than the buffer holds."""
    import jax.numpy as jnp
    rng = np.random.default_rng(n)
    rows = rng.integers(-9, 9, size=(512, 3), dtype=np.int32)
    idx = rng.permutation(512).astype(np.int32)
    idx[n:] = 0
    cap, rb = 200, 64
    got, blocks = bfs._gather_prefix(
        jnp.asarray(rows), jnp.asarray(idx), jnp.int32(n), cap, rb)
    want = np.full((cap, 3), bfs.SENTINEL, np.int32)
    want[:min(n, cap)] = rows[idx[:min(n, cap)]]
    assert np.array_equal(np.asarray(got), want)
    assert bfs._compact_blocks(n, cap, rb) == -(-min(n, cap) // rb) \
        == int(blocks)


# ------------------------------------------- the engine at a block's edges

@pytest.mark.parametrize("floor,edge", [
    (58, "116 = 2 RB, 59 = RB + 1"), (59, "177 = 3 RB, 59 = RB"),
    (88, "177 = 2 RB + 1"), (89, "177 = 2 RB - 1"), (16, "many blocks")])
def test_resident_counts_and_slots_compacted_by_the_one_rule(
        floor, edge, tmp_path, monkeypatch):
    want = _reference().explore(3, 2)
    news = [new for _, _, new in want["levels"]]
    cfg = _toy_cfg(tmp_path, 3, 2)
    res, tel, sess = _explore(TRANSFER, cfg, floor, monkeypatch,
                              no_trace=True, res_caps=dict(CAPS))
    rb = bfs._compact_block_rows(CAPS["AccCap"], CAPS["FCap"])
    assert rb == floor and news[-1] == 0
    assert _answer(res) == _answer(_interp(TRANSFER, cfg)) == \
        (True, want["generated"], want["distinct"], want["diameter"], None)
    assert sess.engine._res_caps == CAPS
    ran = _levels_run(tel)
    assert ran == list(range(len(news)))
    blocks = [bfs._compact_blocks(new, CAPS["FCap"], rb) for new in news]
    assert blocks == [-(-new // rb) for new in news]
    c = tel.counters
    assert c["search.slots_compacted"] == sum(blocks) * rb
    assert c["search.rows_new"] == sum(news) \
        <= c["search.slots_compacted"] < len(news) * CAPS["FCap"]


def test_a_frontier_too_small_rolls_back_grows_and_ends_on_the_counts(
        tmp_path, monkeypatch):
    """FCap 64 holds levels 0 and 1; level 2's 116 new rows do not fit
    (ST_OVF_FRONT: the blocks stop at FCap, the level is rolled back and
    run again in a frontier of 256)."""
    want = _reference().explore(3, 2)
    cfg = _toy_cfg(tmp_path, 3, 2)
    res, tel, sess = _explore(TRANSFER, cfg, 16, monkeypatch, no_trace=True,
                              res_caps=dict(CAPS, FCap=64))
    assert _answer(res) == \
        (True, want["generated"], want["distinct"], want["diameter"], None)
    assert sess.engine._res_caps == CAPS
    ran = _levels_run(tel)
    assert ran.count(2) == 2 and len(ran) == len(want["levels"]) + 1
    # the rolled-back level gathered the 64 rows its frontier held
    news = [new for _, _, new in want["levels"]]
    assert tel.counters["search.slots_compacted"] == 64 + 16 * sum(
        -(-new // 16) for new in news)


# ------------------------------------------------------- with a CONSTRAINT

@pytest.mark.parametrize("floor", [16, 1 << 12], ids=["blocks", "one"])
def test_a_constraint_discards_rows_of_every_level(floor, tmp_path,
                                                   monkeypatch):
    """A 6 x 6 x 6 grid walked one step a time under CONSTRAINT
    x + y =< 4: every level from the fifth on finds rows that stay in
    the seen table and leave the frontier.  The interpreter's counts,
    and both gathers counted: the new rows' and the kept rows'."""
    (tmp_path / "grid.tla").write_text(GRID)
    (tmp_path / "grid.cfg").write_text(
        "SPECIFICATION Spec\nINVARIANT InBox\nCONSTRAINT Near\n")
    spec, cfg = str(tmp_path / "grid.tla"), str(tmp_path / "grid.cfg")
    want = _interp(spec, cfg, no_deadlock=True)
    res, tel, sess = _explore(spec, cfg, floor, monkeypatch, no_trace=True,
                              no_deadlock=True, res_caps=dict(CAPS))
    assert _answer(res) == _answer(want) and res.ok and res.distinct > 60
    assert sess.engine.constraint_fns
    # fingerprinted and discarded: rows of the seen table that are not
    # distinct states
    seen = tel.levels[-1]["seen"]
    assert seen > res.distinct
    rb = bfs._compact_block_rows(CAPS["AccCap"], CAPS["FCap"])
    c = tel.counters
    assert c["search.slots_compacted"] % rb == 0
    assert c["search.slots_compacted"] >= (seen - 1) + c["search.rows_new"]


# ------------------------------------------------------------ traces kept

def test_the_logged_frontier_gives_the_same_trace(tmp_path, reference,
                                                  monkeypatch):
    """The violating cfg at 2 procs / MaxMoney 3, traces kept: the log
    appends the frontier the blocks wrote, and the 7-state trace is the
    one the program with ONE block a level gives, a behaviour by the
    plain reference."""
    cfg = _cfg(tmp_path, ("p1", "p2"), 3)
    caps = {"SC": 1024, "FCap": 256, "AccCap": 1024, "VC": 128}
    res, tel, _ = _explore(VIOLATION, cfg, 16, monkeypatch,
                           res_caps=dict(caps))
    assert bfs._compact_block_rows(caps["AccCap"], caps["FCap"]) == 16
    whole, tel1, _ = _explore(VIOLATION, cfg, 256, monkeypatch,
                              res_caps=dict(caps))
    want = reference.explore(2, 3)
    for got in (res, whole):
        assert (got.violation.kind, got.violation.name) == \
            ("invariant", "NoMoneyCreated")
        assert (got.generated, got.distinct, got.diameter) == \
            (want["generated"], want["distinct"], want["diameter"])
    assert _answer(res) == _answer(whole)
    assert tel.counters["search.rows_new"] == \
        tel1.counters["search.rows_new"]
    assert tel.counters["search.slots_compacted"] < \
        tel1.counters["search.slots_compacted"]
    assert len(res.violation.trace) == 7
    states, labels = _plain(res.violation.trace)
    ok, why = reference.check_trace(states, labels, 2, 3, "NoMoneyCreated",
                                    min_len=7)
    assert ok, why


# ----------------------------------------- capped, reduced, resumed

def test_the_capped_search_spills_and_ends_on_the_counts(tmp_path,
                                                         monkeypatch):
    """`--seen-cap` 512 under 740 distinct states: the table spills, the
    host filters each committed frontier the blocks wrote, and the
    counts are the uncapped run's."""
    want = _reference().explore(3, 2)
    cfg = _toy_cfg(tmp_path, 3, 2)
    res, tel, _ = _explore(TRANSFER, cfg, 16, monkeypatch, no_trace=True,
                           seen_cap=512, res_caps=dict(CAPS, SC=512))
    assert _answer(res) == \
        (True, want["generated"], want["distinct"], want["diameter"], None)
    assert res.tiers["spills"] >= 1 and "cap_breached" not in res.tiers
    assert tel.counters["search.slots_compacted"] >= \
        tel.counters["search.rows_new"]


@pytest.mark.parametrize("cfg", ["portoy_bad", "portoy"])
def test_por_masks_candidates_and_the_blocks_keep_the_verdict(
        cfg, monkeypatch):
    """portoy under --por (masked candidates are invalid rows among the
    accumulator's): the interpreter's verdict, and the counts and depth
    the program with one block a level gives."""
    paths = dict(spec=os.path.join(SPECS, "portoy.tla"),
                 cfg=os.path.join(SPECS, cfg + ".cfg"), por=True)
    caps = {"SC": 1 << 10, "FCap": 256, "AccCap": 1 << 10, "VC": 128}
    res, tel, _ = _explore(floor=16, monkeypatch=monkeypatch,
                           res_caps=dict(caps), **paths)
    whole, tel1, _ = _explore(floor=256, monkeypatch=monkeypatch,
                              res_caps=dict(caps), **paths)
    interp = _interp(**paths)
    assert not res.ok and res.violation.kind == interp.violation.kind \
        == ("invariant" if cfg == "portoy_bad" else "deadlock")
    assert tel.gauges.get("por.device_masked_arms", 0) > 0
    assert _answer(res) == _answer(whole)
    for name in ("search.rows_valid", "search.rows_new",
                 "search.slots_sorted", "search.slots_merged"):
        assert tel.counters[name] == tel1.counters[name], name
    assert tel.counters["search.slots_compacted"] < \
        tel1.counters["search.slots_compacted"]


def test_a_resumed_checkpoint_ends_on_the_full_counts(tmp_path,
                                                      monkeypatch):
    want = _reference().explore(3, 2)
    cfg = _toy_cfg(tmp_path, 3, 2)
    path = str(tmp_path / "toy.ck")
    opts = dict(no_trace=True, res_caps=dict(CAPS))
    cut, _, _ = _explore(TRANSFER, cfg, 16, monkeypatch, max_states=300,
                         checkpoint=path, **opts)
    assert cut.truncated and 300 <= cut.distinct < want["distinct"]
    res, tel, _ = _explore(TRANSFER, cfg, 16, monkeypatch, resume=path,
                           **opts)
    assert _answer(res) == \
        (True, want["generated"], want["distinct"], want["diameter"], None)
    assert tel.counters["search.rows_new"] == \
        want["distinct"] - cut.distinct


# ------------------------------------------------------ the program's text

def _post_merge_compact_ops(sess, scope="compact"):
    """The ops of the resident program's lowered text that sit under
    `jaxmc.compact` in the LEVEL's body and not in a chunk's (whose own
    compaction sorts and takes over the candidate grid)."""
    ex = sess.engine
    caps = ex._res_caps
    fn = ex._get_resident_run(caps["SC"], caps["FCap"], caps["AccCap"],
                              caps["VC"], 64)
    import jax
    import jax.numpy as jnp
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    text = fn.__wrapped__.lower(
        jax.ShapeDtypeStruct((caps["SC"], ex.K), jnp.int32), i32,
        jax.ShapeDtypeStruct((caps["FCap"], ex.PW), jnp.int32),
        *([i32] * 7)).as_text(debug_info=True)
    found = re.compile(
        r'"jit\(run\)/while/body/jaxmc\.%s/([^"]*)"' % scope)
    return [m.group(1) for m in found.finditer(text)]


@pytest.mark.parametrize("constraint", [False, True],
                         ids=["no_constraint", "constraint"])
def test_no_sort_after_the_merge_where_no_constraint_exists(
        constraint, tmp_path, monkeypatch):
    """The one static branch: without a CONSTRAINT the level's
    `jaxmc.compact` after the merge is the block loop alone — no sort,
    no take outside the loop; with one it is the new rows' block loop
    alone as well, and the sort that names the kept rows and the kept
    rows' block loop are there under a scope of their own (ISSUE 51:
    `jaxmc.constraint`)."""
    (tmp_path / "grid.tla").write_text(GRID)
    (tmp_path / "grid.cfg").write_text(
        "SPECIFICATION Spec\nINVARIANT InBox\n"
        + ("CONSTRAINT Near\n" if constraint else ""))
    res, _, sess = _explore(str(tmp_path / "grid.tla"),
                            str(tmp_path / "grid.cfg"), 16, monkeypatch,
                            no_trace=True, no_deadlock=True,
                            res_caps=dict(CAPS))
    assert res.ok and bool(sess.engine.constraint_fns) == constraint
    ops = _post_merge_compact_ops(sess)
    assert "while/body/jit(_take)" in ops and "jit(_take)" not in ops
    assert "sort" not in ops
    judged = _post_merge_compact_ops(sess, "constraint")
    assert ("sort" in judged) == constraint == bool(judged)
    assert ("while/body/jit(_take)" in judged) == constraint
