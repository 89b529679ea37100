"""SYMMETRY over a real group, canonicalised by sorting (ISSUE 47).

`compile/symmetry2.build_canon2` has two forms.  Where the cfg's group is a
product of full symmetric groups (`Permutations(S)`) and the members of S are
mere POSITIONS of the layout, a row is canonicalised by SORTING the
per-member sub-vectors with a fixed compare-exchange network ("sorted");
everywhere else one row transform per group element is unrolled
("unrolled", with its limit and the honest unreduced fallback above it).
Held here, on XLA:CPU at toy sizes:

  * the network sorts (0-1 principle);
  * the two forms give the SAME ROW, bit for bit, on random encoded rows of
    `specs/transfer_symmetry.tla` at 3 and 4 processes: the layout lays each
    variable's lanes out member by member, so the sorted row IS the
    lexicographic minimum of the orbit;
  * the counts of the level and the resident engine equal the plain
    reference's (bench/reference/transfer_symmetry.py) and the exact
    interpreter's (`make_canonicalizer`) at 2x3, 3x4, 4x3, 5x3 and 6x2 —
    groups of 2 to 720 — under TLC's counting (every initial state
    generated); the form is `sorted` and the program does not grow with the
    order of the group;
  * the reference's orbit sum equals the unreduced reference's `distinct`;
  * a model in which a lane HOLDS a member (`owner` in specs/symtoy.tla)
    keeps the unrolled form, and a group over its limit still falls back to
    the unreduced search with the warning;
  * `_program_sig()` tells the forms apart; a cfg without SYMMETRY emits
    none of the names and lowers to a text that holds no canonicaliser;
  * a violating symmetric cfg returns a trace the interpreter replays.
"""

import importlib.util
import itertools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jaxmc import obs
from jaxmc.backend.bfs import SYMMETRY_WARNING, TpuExplorer
from jaxmc.compile import symmetry2
from jaxmc.engine.explore import Explorer
from jaxmc.session import load_model

from test_resident_trace import _replays

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = os.path.join(REPO, "specs")
BENCH_SPECS = os.path.join(REPO, "bench", "specs")
SIZES = [(2, 3), (3, 4), (4, 3), (5, 3), (6, 2)]
IDS = [f"{n}x{m}" for n, m in SIZES]
#: 6 x 2 walks alice down to -10: the default sample's walks do not, and
#: the packed lane then overflows (an exact abort, symmetry or not)
SAMPLE = (4000, 40, 200)


@pytest.fixture(autouse=True)
def _no_capacity_profiles(monkeypatch):
    monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        "plain_" + name, os.path.join(REPO, "bench", kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    return _load("reference", "transfer_symmetry")


def _model(tmp_path, n, m, spec=None, symmetry=True,
           invariants="INVARIANT AliceBounded"):
    cfg = tmp_path / f"t{n}x{m}{'s' if symmetry else 'u'}.cfg"
    cfg.write_text(
        "SPECIFICATION Spec\n%s\n%sCONSTANTS\n  Procs = {%s}\n"
        "  MaxMoney = %d\n" % (
            invariants, "SYMMETRY Perms\n" if symmetry else "",
            ", ".join(f"p{i + 1}" for i in range(n)), m))
    return load_model(spec or os.path.join(SPECS, "transfer_symmetry.tla"),
                      str(cfg), False, includes=[BENCH_SPECS])


def _counts(res):
    assert res.ok and not res.truncated, res.violation
    return res.generated, res.distinct, res.diameter


def _unrolled(engine):
    """The unrolled canonicaliser of an engine's model: what build_canon2
    gives when no bounds report says the members are mere positions."""
    model = engine.model
    report, model._bounds_report = model._bounds_report, None
    try:
        return symmetry2.build_canon2(model, engine.layout)
    finally:
        model._bounds_report = report


# ------------------------------------------------------------ the network

@pytest.mark.parametrize("n", range(2, 10))
def test_the_network_sorts_every_zero_one_input(n):
    net = symmetry2._network(n)
    assert all(0 <= i < j < n for i, j in net)
    for bits in itertools.product((0, 1), repeat=n):
        wires = list(bits)
        for i, j in net:
            if wires[i] > wires[j]:
                wires[i], wires[j] = wires[j], wires[i]
        assert wires == sorted(bits)
    # Batcher's count, not the n(n-1)/2 of a transposition sort
    assert len(net) <= {2: 1, 3: 3, 4: 5, 5: 9, 6: 12, 7: 16, 8: 19,
                        9: 28}[n]


# ---------------------------------------- one row, whichever form made it

@pytest.mark.parametrize("n,m", [(3, 4), (4, 3)], ids=["3x4", "4x3"])
def test_sorted_rows_equal_unrolled_rows_bit_for_bit(tmp_path, n, m):
    engine = TpuExplorer(_model(tmp_path, n, m))
    srt, unr = engine.canon_fn, _unrolled(engine)
    assert (srt.form, unr.form) == ("sorted", "unrolled")
    assert srt.group_order == unr.group_order == [1, 1, 2, 6, 24][n]
    lay = engine.layout
    assert lay.vars == ("alice", "bob", "money", "pc")
    labels = [lay.uni.index(s) for s in ("check", "debit", "credit",
                                         "done")]
    rng = np.random.default_rng(47)
    count = 4096
    rows = np.concatenate([
        rng.integers(-n * m, m + 1, (count, 1)),       # alice, negative too
        rng.integers(0, n * m + 1, (count, 1)),
        rng.integers(1, m + 1, (count, n)),
        rng.choice(labels, (count, n))], axis=1).astype(np.int32)
    rows[:8] = np.asarray([lay.encode(st)
                           for st in engine.init_states[:8]])
    a = np.asarray(jax.jit(srt)(rows))
    b = np.asarray(jax.jit(unr)(rows))
    assert a.dtype == b.dtype == np.int32 and (a == b).all()
    # a representative of the SAME orbit, and the same one for all of it
    pair = lambda r: sorted(zip(r[2:2 + n], r[2 + n:]))   # noqa: E731
    assert all(pair(x) == pair(y) and (x[:2] == y[:2]).all()
               for x, y in zip(rows[:256], a[:256]))
    perm = rng.permutation(n)
    moved = rows.copy()
    moved[:, 2:2 + n] = rows[:, 2:2 + n][:, perm]
    moved[:, 2 + n:] = rows[:, 2 + n:][:, perm]
    assert (np.asarray(jax.jit(srt)(moved)) == a).all()
    assert len(np.unique(a, axis=0)) < len(np.unique(rows, axis=0))


# ------------------------------------------------------------- the counts

@pytest.mark.parametrize("n,m", SIZES, ids=IDS)
def test_the_interpreter_counts_what_the_reference_counts(tmp_path,
                                                          reference, n, m):
    want = reference.explore(n, m)
    got = _counts(Explorer(_model(tmp_path, n, m)).run())
    assert got == (want["generated"], want["distinct"], want["diameter"])
    # every initial state is generated, the orbits are distinct
    assert want["generated"] - sum(c for _, c, _ in want["levels"]) == m ** n
    assert want["levels"][0][0] < m ** n or n == 1


@pytest.mark.parametrize("resident", [False, True],
                         ids=["level", "resident"])
@pytest.mark.parametrize("n,m", SIZES, ids=IDS)
def test_the_engines_count_what_the_reference_counts(tmp_path, reference,
                                                     n, m, resident):
    want = reference.explore(n, m)
    tel = obs.Telemetry(meta={})
    with obs.use(tel):
        engine = TpuExplorer(_model(tmp_path, n, m), resident=resident,
                             store_trace=not resident, sample_cfg=SAMPLE)
        res = engine.run()
    assert engine.sym_form == engine.canon_fn.form == "sorted"
    assert not [w for w in res.warnings if "SYMMETRY" in w]
    assert _counts(res) == (want["generated"], want["distinct"],
                            want["diameter"])
    snap = tel.metrics_snapshot()
    assert snap["gauges"]["symmetry.form"] == "sorted"
    assert snap["gauges"]["symmetry.group_order"] == \
        [1, 1, 2, 6, 24, 120, 720][n]
    assert snap["counters"]["search.canon_rows"] == want["generated"]


def _keys_text(engine, rows=256):
    keys_of = engine._keys_fn()
    return jax.jit(lambda r, v: keys_of(r, v)).lower(
        jax.ShapeDtypeStruct((rows, engine.W), jnp.int32),
        jax.ShapeDtypeStruct((rows,), jnp.bool_)).as_text(debug_info=True)


def test_the_program_does_not_grow_with_the_group(tmp_path):
    """|G| = 6, 120, 720: the keys program (the canonicaliser in front of
    pack and fingerprint) grows with the NETWORK — 3, 9, 12 comparators of
    2-lane pairs — and the 720-element group's is a small constant times
    the 6-element group's, where the unrolled form's grows 5x a process
    (52 KB, 285 KB, 1.76 MB of text at 3, 4, 5 processes)."""
    texts = {n: _keys_text(TpuExplorer(_model(tmp_path, n, 2)))
             for n in (3, 5, 6)}
    assert all("jaxmc.canon" in t for t in texts.values())
    assert len(texts[5]) < 3 * len(texts[3])
    assert len(texts[6]) < 4 * len(texts[3])
    unrolled = TpuExplorer(_model(tmp_path, 3, 2))
    unrolled.canon_fn = _unrolled(unrolled)
    assert len(_keys_text(unrolled)) > 2 * len(texts[3])


@pytest.mark.parametrize("n,m", [(3, 4), (4, 3)], ids=["3x4", "4x3"])
def test_the_orbit_sum_is_the_unreduced_model(reference, n, m):
    unreduced = _load("reference", "transfer_scaled").explore(n, m)
    assert reference.unreduced_distinct(n, m) == unreduced["distinct"]
    assert reference.explore(n, m)["distinct"] < unreduced["distinct"]


def test_the_reference_refuses_a_cfg_without_the_symmetry_line(reference):
    cfg = open(os.path.join(BENCH_SPECS, "transfer_symmetry_5p.cfg")).read()
    assert reference.parse_cfg(cfg) == (5, 12, ["AliceBounded"])
    with pytest.raises(ValueError, match="SYMMETRY"):
        reference.parse_cfg(cfg.replace("SYMMETRY Perms\n", ""))
    # narrower keys merge orbits: the control of `correct`
    full = reference.explore(3, 4)
    bits = reference.state_bits(3, 4)
    assert reference.explore(3, 4, key_bits=bits) == full
    assert reference.explore(3, 4, key_bits=bits - 4)["distinct"] \
        < full["distinct"]


# ------------------------------------- where the sorted form does not apply

def _symtoy(tmp_path, n):
    cfg = tmp_path / f"symtoy{n}.cfg"
    cfg.write_text("SPECIFICATION Spec\nCONSTANTS\n  P = {%s}\n"
                   "  None = None\nSYMMETRY Perms\nINVARIANT TypeInv\n"
                   % ", ".join(f"p{i + 1}" for i in range(n)))
    return load_model(os.path.join(SPECS, "symtoy.tla"), str(cfg), True)


def test_a_lane_that_holds_a_member_keeps_the_unrolled_form(tmp_path):
    model = _symtoy(tmp_path, 3)
    engine = TpuExplorer(model)
    assert engine.sym_form == engine.canon_fn.form == "unrolled"
    assert engine.canon_fn.group_order == 6
    with pytest.raises(symmetry2._NotSortable, match="owner can hold"):
        symmetry2._member_lanes(model, engine.layout,
                                sorted(model.defs["P"], key=str))
    res = engine.run()
    assert (res.generated, res.distinct) == (33, 22) and not res.warnings


def test_a_group_over_the_limit_there_still_falls_back(tmp_path):
    tel = obs.Telemetry(meta={})
    with obs.use(tel):
        engine = TpuExplorer(_symtoy(tmp_path, 5))
    assert engine.canon_fn is None and engine.sym_form == "none"
    assert "119 non-identity elements" in engine._sym_fallback
    (warning,) = engine._symmetry_warnings()
    assert warning.startswith(SYMMETRY_WARNING)
    gauges = tel.metrics_snapshot()["gauges"]
    assert (gauges["symmetry.form"], gauges["symmetry.group_order"]) == \
        ("none", 1)


def test_the_signature_tells_the_forms_apart(tmp_path, monkeypatch):
    sorted_engine = TpuExplorer(_model(tmp_path, 3, 3), resident=True,
                                store_trace=False)

    def refuse(*_):
        raise symmetry2._NotSortable("for the test")
    with monkeypatch.context() as patch:
        patch.setattr(symmetry2, "_member_lanes", refuse)
        unrolled_engine = TpuExplorer(_model(tmp_path, 3, 3),
                                      resident=True, store_trace=False)
    assert (sorted_engine.sym_form, unrolled_engine.sym_form) == \
        ("sorted", "unrolled")
    sigs = sorted_engine._program_sig(), unrolled_engine._program_sig()
    assert None not in sigs and sigs[0] != sigs[1]
    # one model, one form: the same signature again
    again = TpuExplorer(_model(tmp_path, 3, 3), resident=True,
                        store_trace=False)
    assert again._program_sig() == sigs[0]


def test_a_cfg_without_symmetry_knows_nothing_of_it(tmp_path):
    tel = obs.Telemetry(meta={})
    with obs.use(tel):
        plain = TpuExplorer(_model(tmp_path, 3, 3, symmetry=False,
                                   spec=os.path.join(
                                       SPECS, "transfer_scaled.tla")),
                            resident=True, store_trace=False)
        res = plain.run()
    assert plain.canon_fn is None and plain.sym_form == "none"
    snap = tel.metrics_snapshot()
    assert not [k for k in list(snap["gauges"]) + list(snap["counters"])
                if k.startswith("symmetry.") or k == "search.canon_rows"]
    assert "jaxmc.canon" not in _keys_text(plain)
    # the same module under the SYMMETRY line: the same program but for
    # the canonicaliser, fewer states
    sym = TpuExplorer(_model(tmp_path, 3, 3), resident=True,
                      store_trace=False)
    assert sym.layout.specs == plain.layout.specs
    assert (sym.A, sym.W, sym.PW, sym.K) == (plain.A, plain.W, plain.PW,
                                             plain.K)
    assert sym.run().distinct < res.distinct


# ------------------------------------------------- a violating symmetric cfg

@pytest.mark.parametrize("resident", [False, True],
                         ids=["level", "resident"])
def test_a_violating_symmetric_cfg_returns_a_trace_that_replays(tmp_path,
                                                                resident):
    spec = tmp_path / "transfer_violation_symmetry.tla"
    spec.write_text(
        "---- MODULE transfer_violation_symmetry ----\n"
        "EXTENDS transfer_violation, TLC\n"
        "Perms == Permutations(Procs)\n====\n")
    model = _model(tmp_path, 3, 3, spec=str(spec),
                   invariants="INVARIANTS AliceBounded NoMoneyCreated")
    want = Explorer(model).run()
    engine = TpuExplorer(model, resident=resident)
    assert engine.sym_form == "sorted"
    res = engine.run()
    assert not res.ok and not want.ok
    assert (res.violation.kind, res.violation.name) == \
        (want.violation.kind, want.violation.name) == \
        ("invariant", "NoMoneyCreated")
    # a shortest counterexample (six steps: two processes race), made of
    # STORED states — one member per orbit — each a step of Next
    assert len(res.violation.trace) == len(want.violation.trace) == 7
    _replays(model, res.violation.trace)
    assert res.violation.trace[-1][0]["bob"] > 3
