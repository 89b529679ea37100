r"""A spec bounded by the cfg's CONSTRAINT alone (ISSUE 51).

`specs/transfer_retry.tla` EXTENDS the transfer race and lets a finished
transfer be retried, counting the retries in `tries`, which nothing in the
spec bounds: the cfg's `CONSTRAINT TriesBounded` alone makes the model
finite.  TLC's rule under a CONSTRAINT: a violating successor is generated
and fingerprinted, then discarded — not distinct, not invariant-checked,
never explored.  Held here, on XLA:CPU at toy sizes, limit 0:

* the plain reference (`bench/reference/transfer_retry.py`, numpy, nothing
  of jaxmc) = the interpreter = the level engine = the resident engine, in
  `generated`, `distinct`, `diameter` and the rows DISCARDED;
* the device engines' `search.rows_discarded` and
  `search.slots_constrained` against the reference's levels by the one rule,
  and the gauge `constraint.compiled`;
* a lowered resident program holds the scope `jaxmc.constraint` if and only
  if the cfg has a CONSTRAINT;
* the same cfg under `--seen-cap`, with traces kept, after a resume and on
  a two-device mesh (`finish_scatter`) ends on the same counts;
* a violating variant reports a trace whose every state satisfies the
  constraint; `ACTION-CONSTRAINT` is still refused by name.
"""

import importlib.util
import os
import shutil

import pytest

pytest.importorskip("jax")

from jaxmc import obs  # noqa: E402
from jaxmc.backend import bfs  # noqa: E402
from jaxmc.session import CheckSession, SessionConfig  # noqa: E402

from test_resident_trace import _plain  # noqa: E402
from test_sort_ladder import _levels_run  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = os.path.join(REPO, "specs")
RETRY = os.path.join(SPECS, "transfer_retry.tla")
TRANSFER = os.path.join(SPECS, "transfer_scaled.tla")
# every capacity holds every toy model below: nothing grows, and the slots
# are levels x AccCap
CAPS = {"SC": 1 << 15, "FCap": 1 << 12, "AccCap": 1 << 13, "VC": 256}
# (procs, MaxMoney, MaxTries): generated, distinct, diameter, rows that
# entered the seen table, rows of those the constraint discarded — ISSUE
# 51's numbers, which the committed reference recomputes here
SIZES = {
    (3, 2, 1): (16553, 5515, 17, 8734, 3219),
    (2, 3, 2): (2587, 1289, 18, 1655, 366),
    (2, 4, 2): (4868, 2426, 20, 3138, 712),
    (2, 2, 3): (1752, 874, 20, 1062, 188),
    (3, 3, 1): (50712, 16895, 19, 27356, 10461),
}
#: minutes on the interpreter: the two device engines alone
DEVICE_ONLY = {(3, 3, 1)}


@pytest.fixture(autouse=True)
def _no_capacity_profiles(monkeypatch):
    monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "plain_reference_retry", os.path.join(
            REPO, "bench", "reference", "transfer_retry.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg_text(procs, max_money, max_tries, constraint=True,
              invariant="AliceBounded"):
    return ("SPECIFICATION SpecR\nINVARIANT %s\n%sCONSTANTS\n"
            "  Procs = {%s}\n  MaxMoney = %d\n  MaxTries = %d\n" % (
                invariant,
                "CONSTRAINT TriesBounded\n" if constraint else "",
                ", ".join("p%d" % (i + 1) for i in range(procs)),
                max_money, max_tries))


def _cfg(tmp_path, size, **kw):
    path = tmp_path / "retry.cfg"
    path.write_text(_cfg_text(*size, **kw))
    return str(path)


def _check(spec, cfg, **opts):
    """The normal path: a CheckSession.  (result, telemetry, session)"""
    if opts.get("backend") != "interp":
        opts = dict(dict(backend="jax", platform="cpu", chunk=64), **opts)
    tel = obs.Telemetry(meta={})
    with obs.use(tel):
        sess = CheckSession(SessionConfig(spec=spec, cfg=cfg, **opts),
                            tel=tel)
        return sess.explore(), tel, sess


RESIDENT = dict(resident=True, no_trace=True, res_caps=dict(CAPS))
ENGINES = {"interp": dict(backend="interp"), "level": dict(),
           "resident": RESIDENT}


def _counts(res):
    return (res.generated, res.distinct, res.diameter, bool(res.ok))


# -------------------------------------------- the reference and the cfg

def test_the_reference_counts_as_tlc_does_under_a_constraint(reference):
    for size, (gen, dist, diam, fingerprinted, gone) in SIZES.items():
        ref = reference.explore(size[0], size[1:])
        assert (ref["generated"], ref["distinct"], ref["diameter"],
                ref["ok"]) == (gen, dist, diam, True), size
        assert (ref["fingerprinted"], ref["discarded"]) == \
            (fingerprinted, gone) == (dist + gone, gone), size
        levels, entered = ref["levels"], ref["fingerprinted_levels"]
        # every state has one successor a process; `new` counts the rows
        # KEPT, which `distinct` sums; the discards are the rest
        assert all(cand == size[0] * f for f, cand, _ in levels)
        init = levels[0][0]
        assert init == size[1] ** size[0]
        assert dist == init + sum(new for _, _, new in levels)
        assert fingerprinted == init + sum(entered)
        assert all(e >= new for e, (_, _, new) in zip(entered, levels))
        assert [f for f, _, _ in levels[1:]] == \
            [new for _, _, new in levels[:-1]]
    # by hand, 1 process, MaxMoney 1, MaxTries 0: check, debit, credit,
    # done, and the retry that the constraint discards
    one = reference.explore(1, (1, 0))
    assert (one["generated"], one["distinct"], one["diameter"],
            one["discarded"]) == (5, 4, 3, 1)
    assert one["levels"] == [[1, 1, 1], [1, 1, 1], [1, 1, 1], [1, 1, 0]]
    assert one["fingerprinted_levels"] == [1, 1, 1, 1]


def test_the_reference_reads_the_cfg_and_refuses_one_without_the_line(
        reference):
    text = _cfg_text(3, 2, 1)
    assert reference.parse_cfg(text) == (3, (2, 1), ["AliceBounded"])
    assert reference.parse_cfg(open(os.path.join(
        SPECS, "transfer_retry_3p.cfg")).read()) == \
        (3, (2, 1), ["AliceBounded"])
    with pytest.raises(ValueError, match="CONSTRAINT"):
        reference.parse_cfg(_cfg_text(3, 2, 1, constraint=False))
    # a narrowed key merges states: the control of the benchmark's correct
    exact = reference.explore(3, (2, 1))
    narrow = reference.explore(
        3, (2, 1), key_bits=reference.state_bits(3, (2, 1)) - 4)
    assert narrow["distinct"] < exact["distinct"]


def test_the_copies_under_specs_are_the_benchmarks():
    for name in ("transfer_retry.tla", "transfer_scaled.tla"):
        assert open(os.path.join(SPECS, name)).read() == open(
            os.path.join(REPO, "bench", "specs", name)).read()
    body = [ln for ln in open(RETRY).read().splitlines()
            if ln.strip() and not ln.startswith(("\\*", "---", "==="))]
    assert body[0] == "EXTENDS transfer_scaled"
    assert "TriesBounded == \\A p \\in Procs : tries[p] <= MaxTries" in body


# ------------------------- reference = interpreter = level = resident

@pytest.mark.parametrize("size,engine", [
    pytest.param(size, engine, id="%dx%dx%d-%s" % (size + (engine,)))
    for size in sorted(SIZES) for engine in sorted(ENGINES)
    if not (engine == "interp" and size in DEVICE_ONLY)])
def test_every_engine_gives_the_references_counts_and_discards(
        size, engine, tmp_path, reference):
    ref = reference.explore(size[0], size[1:])
    res, tel, sess = _check(RETRY, _cfg(tmp_path, size), **ENGINES[engine])
    assert _counts(res) == (ref["generated"], ref["distinct"],
                            ref["diameter"], True) == \
        SIZES[size][:3] + (True,)
    assert res.violation is None and not res.truncated
    if engine == "interp":
        # the seen set holds the discarded fingerprints too
        assert tel.gauges["fingerprint.occupancy"] - res.distinct == \
            ref["discarded"]
        return
    eng, c = sess.engine, tel.counters
    assert [nm for nm, _ in eng.constraint_fns] == ["TriesBounded"]
    assert eng.fb_cons == [] and not eng.hybrid
    assert tel.gauges["constraint.compiled"] == 1
    assert tel.gauges["expand.constraints_interp"] == 0
    assert tel.gauges["analyze.bounds_converged"] is True
    init = ref["levels"][0][0]
    assert c["search.rows_discarded"] == ref["discarded"] == SIZES[size][4]
    assert c["search.rows_new"] == ref["distinct"] - init
    assert c["search.rows_new"] + c["search.rows_discarded"] == \
        sum(ref["fingerprinted_levels"])
    assert c["search.rows_valid"] == ref["generated"] - init
    if engine == "resident":
        # levels run x AccCap: the predicates and the sort over every slot
        assert eng._res_caps == CAPS
        assert _levels_run(tel) == list(range(len(ref["levels"])))
        assert c["search.slots_constrained"] == \
            len(ref["levels"]) * CAPS["AccCap"]
    else:
        # the candidate block a level, the slots its key sort is given
        assert c["search.slots_constrained"] == c["search.slots_sorted"]
        assert c["search.slots_constrained"] >= \
            sum(cand for _, cand, _ in ref["levels"])


def test_a_second_search_of_the_session_counts_the_same(tmp_path,
                                                        reference):
    """The benchmark's window: `explore()` again on the engine the warm-up
    drove; the counters rise by the same amounts."""
    ref = reference.explore(2, (3, 2))
    tel = obs.Telemetry(meta={})
    with obs.use(tel):
        sess = CheckSession(SessionConfig(
            spec=RETRY, cfg=_cfg(tmp_path, (2, 3, 2)), backend="jax",
            platform="cpu", chunk=64, **RESIDENT), tel=tel)
        for n in (1, 2):
            assert _counts(sess.explore())[:2] == (ref["generated"],
                                                   ref["distinct"])
            assert tel.counters["search.rows_discarded"] == \
                n * ref["discarded"]
            assert tel.counters["search.slots_constrained"] == \
                n * len(ref["levels"]) * CAPS["AccCap"]


def test_a_rolled_back_level_counts_its_slots_and_no_discard_twice(
        tmp_path, reference):
    """FCap 64 is too small for 3 x 2 x 1: levels roll back and run again
    in a frontier that grew.  The discards are counted once (a rolled-back
    level leaves the table as it was), the slots for every level run."""
    ref = reference.explore(3, (2, 1))
    caps = dict(CAPS, FCap=64, AccCap=1 << 11)
    res, tel, sess = _check(RETRY, _cfg(tmp_path, (3, 2, 1)),
                            resident=True, no_trace=True, res_caps=caps)
    assert _counts(res) == (ref["generated"], ref["distinct"],
                            ref["diameter"], True)
    ran = _levels_run(tel)
    assert len(ran) > len(ref["levels"]) and sess.engine._res_caps != caps
    assert tel.counters["search.rows_discarded"] == ref["discarded"]
    grown = [rec for rec in tel.levels if rec["status"] in (
        bfs.ST_OVF_FRONT, bfs.ST_OVF_ACC, bfs.ST_OVF_SEEN, bfs.ST_OVF_VC)]
    assert grown
    assert tel.counters["search.slots_constrained"] >= \
        len(ran) * caps["AccCap"]


# ---------------------------------------------------- the lowered text

def _lowered(spec, cfg):
    """The resident program's lowered text, debug info kept, at CAPS."""
    tel = obs.Telemetry(meta={})
    with obs.use(tel):
        sess = CheckSession(SessionConfig(
            spec=spec, cfg=cfg, backend="jax", platform="cpu", chunk=64,
            **RESIDENT), tel=tel)
        sess.compile()
        eng = sess.engine
        import jax
        import jax.numpy as jnp
        fn = eng._get_resident_run(CAPS["SC"], CAPS["FCap"],
                                   CAPS["AccCap"], CAPS["VC"], 64)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa
        args = (i32(CAPS["SC"], eng.K), i32(),
                i32(CAPS["FCap"], eng.PW)) + (i32(),) * 7
        low = fn.__wrapped__.lower(*args)
        return low.as_text(debug_info=True), eng


def test_the_scope_is_in_the_program_iff_the_cfg_has_a_constraint(tmp_path):
    with_c, eng = _lowered(RETRY, _cfg(tmp_path, (2, 2, 1)))
    assert eng.constraint_fns and "jaxmc.constraint" in with_c
    # the first gather of the new rows stays the compaction's, and the
    # invariants' scan keeps its name
    assert "jaxmc.compact" in with_c and "jaxmc.scan" in with_c
    # the same module WITHOUT the line (an infinite model: never run, only
    # lowered) and the spec every other cell checks: no such name
    plain = tmp_path / "plain.cfg"
    plain.write_text(_cfg_text(2, 2, 1, constraint=False))
    without, eng = _lowered(RETRY, str(plain))
    assert not eng.constraint_fns and "jaxmc.constraint" not in without
    scaled = tmp_path / "scaled.cfg"
    scaled.write_text("SPECIFICATION Spec\nINVARIANT AliceBounded\n"
                      "CONSTANTS\n  Procs = {p1, p2}\n  MaxMoney = 2\n")
    text, eng = _lowered(TRANSFER, str(scaled))
    assert not eng.constraint_fns and "jaxmc.constraint" not in text
    assert "jaxmc.scan" in text and "jaxmc.compact" in text


# ------------------------------------------------ the 128-bit dedup key

def _keys(rows):
    import jax.numpy as jnp
    import numpy as np
    return np.asarray(bfs.fingerprint128(
        jnp.asarray(np.asarray(rows, np.uint32).view(np.int32)))
    ).view(np.uint32)


def test_the_two_states_the_old_fingerprint_merged_get_two_keys():
    """desk-constraint-4p, levels 13 and 14: pc[p2] done / check and
    tries[p4] 0 / 1, the rest equal — bits 26-31 of the two packed words.
    The four FNV lanes of the fingerprint up to ISSUE 51 met on the pair and
    every search on the chip ended one state short of the reference."""
    a, b = _keys([[487343373, 272713316], [286016781, 339822180]])
    assert (a != b).all()


@pytest.mark.parametrize("words", [1, 2, 3, 4])
def test_a_key_basis_of_four_words_or_fewer_is_permuted_not_hashed(words):
    """Rows that differ in the TOP bits of their words alone — a product
    carries a difference upward only, so the old lanes differed in their top
    bits alone and 64 of their bits collided by the thousand: here every
    PAIR of key words tells all the rows apart (the whole key is a bijection
    of the row)."""
    import numpy as np
    top = (np.arange(4096, dtype=np.uint32) % 64) << np.uint32(26)
    more = (np.arange(4096, dtype=np.uint32) // 64) << np.uint32(26)
    rows = np.zeros((4096, words), np.uint32) + np.uint32(0x1234567)
    rows[:, 0] ^= top
    if words > 1:
        rows[:, -1] ^= more
    else:
        rows[:, 0] ^= more >> np.uint32(6)
    assert len(np.unique(rows, axis=0)) == 4096
    k = _keys(rows).astype(np.uint64)
    for i in range(4):
        for j in range(i + 1, 4):
            assert len(np.unique(k[:, i] << np.uint64(32) | k[:, j])) == 4096


def test_a_wide_row_reaches_every_key_word_from_every_word():
    """Past four words the state absorbs and is permuted again: flipping
    one bit of ANY word changes all four key words."""
    import numpy as np
    base = np.arange(9, dtype=np.uint32) * np.uint32(0x01010101)
    rows = np.stack([base] + [base ^ (np.uint32(1 << (5 * w % 32))
                                      * (np.arange(9) == w))
                              for w in range(9)]).astype(np.uint32)
    k = _keys(rows)
    assert (k[1:] != k[0]).all()


def test_a_cfg_without_a_constraint_emits_none_of_the_names(tmp_path):
    scaled = tmp_path / "scaled.cfg"
    scaled.write_text("SPECIFICATION Spec\nINVARIANT AliceBounded\n"
                      "CONSTANTS\n  Procs = {p1, p2}\n  MaxMoney = 3\n")
    for opts in (RESIDENT, {}):
        res, tel, _ = _check(TRANSFER, str(scaled), **opts)
        assert res.ok
        assert not [k for k in list(tel.counters) + list(tel.gauges)
                    if "constrained" in k or "discarded" in k
                    or k.startswith("constraint.")]
        assert tel.gauges["expand.constraints_interp"] == 0


# -------------------- capped, traced, resumed, on a mesh: the same counts

def _resumed(tmp_path, cfg):
    path = str(tmp_path / "retry.ck")
    cut, _, _ = _check(RETRY, cfg, max_states=1500, checkpoint=path,
                       **RESIDENT)
    assert cut.truncated and 1500 <= cut.distinct < 5515
    return _check(RETRY, cfg, resume=path, **RESIDENT)


WAYS = {
    # 8,734 rows enter the table: a cap of 4,096 spills
    "seen_cap": lambda tmp, cfg: _check(
        RETRY, cfg, resident=True, no_trace=True, seen_cap=1 << 12,
        res_caps=dict(CAPS, SC=1 << 12)),
    "seen_cap_level": lambda tmp, cfg: _check(RETRY, cfg,
                                              seen_cap=1 << 12),
    "traces_kept": lambda tmp, cfg: _check(
        RETRY, cfg, resident=True, res_caps=dict(CAPS)),
    "resumed": _resumed,
    "mesh": lambda tmp, cfg: _check(RETRY, cfg, devices=2),
}


@pytest.mark.parametrize("way", sorted(WAYS))
def test_the_same_cfg_another_way_ends_on_the_same_counts(way, tmp_path,
                                                          reference):
    ref = reference.explore(3, (2, 1))
    res, tel, sess = WAYS[way](tmp_path, _cfg(tmp_path, (3, 2, 1)))
    assert _counts(res) == (ref["generated"], ref["distinct"],
                            ref["diameter"], True)
    assert not res.truncated and res.violation is None
    c = tel.counters
    if way.startswith("seen_cap"):
        assert res.tiers["spills"] >= 1
        # a state that was spilled, met again and discarded again counts
        # again: never fewer than the model's
        assert c["search.rows_discarded"] >= ref["discarded"]
    elif way == "mesh":
        assert tel.gauges["mesh.finish_form"] == "scatter"
        assert c["search.rows_discarded"] == ref["discarded"]
        assert c["search.slots_constrained"] == c["search.slots_sorted"]
    elif way == "traces_kept":
        assert c["search.rows_discarded"] == ref["discarded"]
        assert c["search.log_rows"] >= ref["distinct"] - ref["levels"][-1][0]
    else:
        # the resumed half alone
        assert 0 < c["search.rows_discarded"] < ref["discarded"]


def test_a_checkpoint_keyed_by_another_fingerprint_is_refused(tmp_path,
                                                              monkeypatch):
    """A checkpoint's seen table holds keys; only the function that made
    them meets them again.  One written before ISSUE 51 names none."""
    from jaxmc.engine.ckpt import CkptError
    cfg = _cfg(tmp_path, (2, 2, 1))
    path = str(tmp_path / "old.ck")
    with monkeypatch.context() as m:
        m.setattr(bfs, "KEY_FN", None)
        cut, _, _ = _check(RETRY, cfg, max_states=20, checkpoint=path,
                           **RESIDENT)
    assert cut.truncated
    with pytest.raises(CkptError, match="another fingerprint function"):
        _check(RETRY, cfg, resume=path, **RESIDENT)


# ------------------------------------------------ a violating variant

BROKEN = """---- MODULE retry_broken ----
EXTENDS transfer_retry
\\* false once a process that has retried reaches "credit" again: six
\\* steps from an initial state, one of them a Retry
FirstTryOnly == \\A p \\in Procs : tries[p] = 0 \\/ pc[p] # "credit"
====
"""


@pytest.mark.parametrize("engine", ["interp", "level", "resident"])
def test_a_violations_trace_holds_only_states_the_constraint_keeps(
        engine, tmp_path):
    for name in ("transfer_retry.tla", "transfer_scaled.tla"):
        shutil.copy(os.path.join(SPECS, name), tmp_path / name)
    (tmp_path / "retry_broken.tla").write_text(BROKEN)
    cfg = _cfg(tmp_path, (2, 2, 1), invariant="FirstTryOnly")
    opts = dict(ENGINES[engine])
    if engine == "resident":
        opts.pop("no_trace")           # the state log and the walk back
    res, _, _ = _check(str(tmp_path / "retry_broken.tla"), cfg, **opts)
    assert not res.ok and (res.violation.kind, res.violation.name) == \
        ("invariant", "FirstTryOnly")
    states, labels = _plain(res.violation.trace)
    assert len(states) == 7        # the shortest: BFS on every engine
    assert all(max(st["tries"].values()) <= 1 for st in states)
    last = states[-1]
    assert [p for p in last["pc"] if last["pc"][p] == "credit"
            and last["tries"][p] == 1]
    assert sum(1 for lb in labels[1:] if "Retry" in str(lb)) >= 1


def test_an_action_constraint_is_still_refused_by_name(tmp_path):
    for name in ("transfer_retry.tla", "transfer_scaled.tla"):
        shutil.copy(os.path.join(SPECS, name), tmp_path / name)
    (tmp_path / "ac.tla").write_text(
        "---- MODULE ac ----\nEXTENDS transfer_retry\n"
        "Slowly == \\A p \\in Procs : tries'[p] <= tries[p] + 1\n====\n")
    cfg = tmp_path / "ac.cfg"
    cfg.write_text(_cfg_text(2, 2, 1).replace(
        "CONSTANTS", "ACTION-CONSTRAINT Slowly\nCONSTANTS"))
    with pytest.raises(Exception, match="action constraints not compiled"):
        _check(str(tmp_path / "ac.tla"), str(cfg), **RESIDENT)
    res, _, _ = _check(str(tmp_path / "ac.tla"), str(cfg),
                       backend="interp")
    assert res.ok and res.distinct == 282
