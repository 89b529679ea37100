r"""Bit-packed state lane tests (ISSUE 6, compile/pack.py).

Three layers:
  1. LanePlan round-trip property tests per value shape — every vspec
     kind (seq zero-padding, growset/kvtable SENTINEL padding, union
     overlays, pfcn present/absent) must pack/unpack to the identical
     lane row, host (numpy) and device (jnp) paths agreeing.
  2. Injectivity: distinct lane rows pack to distinct packed rows
     (packed equality == state equality — the exact-dedup guarantee).
  3. Whole-engine parity on the repo-local fixtures: packed and
     unpacked (JAXMC_PACK=0) layouts must produce bit-identical
     generated/distinct counts — and identical counterexample TRACES —
     against the exact interpreter, across the level, resident and
     host_seen device modes.
"""

import os

import numpy as np
import pytest

from conftest import REFERENCE  # noqa: F401  (path side effects)

from jaxmc.front.cfg import parse_cfg
from jaxmc.sem.modules import Loader, bind_model
from jaxmc.engine.explore import Explorer
from jaxmc.engine.simulate import sample_states
from jaxmc.compile.kernel2 import build_layout2
from jaxmc.compile.pack import build_lane_plan, packing_enabled
from jaxmc.compile.vspec import Bounds, SENTINEL_LANE

SPECS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "specs")

FIXTURES = {
    "viewtoy": ("viewtoy.tla", "viewtoy.cfg", False),
    "symtoy": ("symtoy.tla", "symtoy.cfg", True),
    "constoy": ("constoy.tla", "constoy.cfg", False),
    "interparm_toy": ("interparm_toy.tla", "interparm_toy.cfg", False),
}


def load(name):
    spec, cfg, no_dl = FIXTURES[name]
    m = bind_model(Loader([SPECS]).load_path(os.path.join(SPECS, spec)),
                   parse_cfg(open(os.path.join(SPECS, cfg)).read()))
    if no_dl:
        m.check_deadlock = False
    return m


def layout_and_rows(name, bfs=300, walks=20, depth=30):
    m = load(name)
    sampled = list(sample_states(m, bfs_states=bfs, n_walks=walks,
                                 walk_depth=depth))
    lay, _ = build_layout2(m, sampled, Bounds())
    rows = np.stack([lay.encode(st) for st in sampled])
    return m, lay, rows


# ---------------------------------------------------------------- layer 1

@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_roundtrip_fixture_layouts(name):
    _m, lay, rows = layout_and_rows(name)
    plan = lay.plan
    back = plan.unpack_np(plan.pack_np(rows))
    assert (back == rows).all(), f"{name}: np pack/unpack not inverse"
    # device path agrees with the host path bit for bit
    import jax
    import jax.numpy as jnp
    pk, ovf = jax.jit(plan.pack_rows)(jnp.asarray(rows))
    assert not bool(np.asarray(ovf).any())
    assert (np.asarray(pk) == plan.pack_np(rows)).all()
    assert (np.asarray(jax.jit(plan.unpack_rows)(pk)) == rows).all()


def test_roundtrip_container_shapes():
    """One synthetic layout covering the shape zoo: seq (zero-padded
    tails), growset + kvtable (SENTINEL-padded slots), pfcn
    (present/absent), union (overlaid payloads), set membership."""
    from jaxmc.sem.values import Fcn, mk_seq
    from jaxmc.compile.vspec import (EnumUniverse, apply_bounds, infer,
                                     merge, encode as vs_encode)
    uni = EnumUniverse()
    vals = [
        mk_seq(["a", "b"]),                     # seq of enums, len 2
        mk_seq([]),                             # zero-padded empty seq
        frozenset({1, 5}),                      # growset of ints
        frozenset(),                            # empty -> all-sentinel
        Fcn({"k": 3}),                          # record variant 1
        Fcn({"t": True, "u": 0}),               # record variant 2
    ]
    specs = []
    for group in ((vals[0], vals[1]), (vals[2], vals[3]),
                  (vals[4], vals[5])):
        sp = None
        for v in group:
            s = infer(v, uni)
            sp = s if sp is None else merge(sp, s)
        specs.append(apply_bounds(sp, Bounds()))

    class FakeLayout:
        vars = ("s", "g", "u")
        width = sum(s.width for s in specs)
        uni2 = uni

        def __init__(self):
            self.specs = dict(zip(self.vars, specs))
            self.uni = uni

    lay = FakeLayout()
    rows = []
    for s, g, u in [(vals[0], vals[2], vals[4]),
                    (vals[1], vals[3], vals[5]),
                    (vals[0], vals[3], vals[5]),
                    (vals[1], vals[2], vals[4])]:
        out = []
        vs_encode(s, specs[0], uni, out)
        vs_encode(g, specs[1], uni, out)
        vs_encode(u, specs[2], uni, out)
        rows.append(np.asarray(out, np.int32))
    rows = np.stack(rows)
    assert (rows == SENTINEL_LANE).any(), "fixture must exercise padding"
    plan = build_lane_plan(lay, list(rows))
    assert not plan.identity, "the shape zoo must actually pack"
    assert plan.packed_width < lay.width
    back = plan.unpack_np(plan.pack_np(rows))
    assert (back == rows).all()


def test_packing_is_injective():
    _m, lay, rows = layout_and_rows("symtoy")
    uniq = np.unique(rows, axis=0)
    packed = lay.plan.pack_np(uniq)
    assert len(np.unique(packed, axis=0)) == len(uniq), \
        "two distinct lane rows packed to the same row"


def test_identity_plan_under_env(monkeypatch):
    monkeypatch.setenv("JAXMC_PACK", "0")
    assert not packing_enabled()
    _m, lay, rows = layout_and_rows("constoy")
    assert lay.plan.identity
    assert lay.plan.packed_width == lay.width
    assert (lay.plan.pack_np(rows) == rows).all()


def test_pack_overflow_guard_raises():
    _m, lay, rows = layout_and_rows("constoy")
    plan = lay.plan
    guarded = np.nonzero(plan.guarded)[0]
    if not len(guarded):
        pytest.skip("no guarded lanes in this layout")
    from jaxmc.compile.vspec import CompileError
    bad = rows[:1].copy()
    i = int(guarded[0])
    bad[0, i] = int(plan.bias[i] + plan.allowed[i] + 1)
    with pytest.raises(CompileError, match="packed lane"):
        plan.pack_np(bad)
    # the device path reports, never raises (engines route to OV_PACK)
    import jax.numpy as jnp
    _pk, ovf = plan.pack_rows(jnp.asarray(bad))
    assert bool(np.asarray(ovf)[0])


@pytest.mark.parametrize("seen, packed_for", [
    # never seen below 0 — a count or a length: one span below, the extra
    # codes above, as since ISSUE 6
    ((0, 5), (-5, 58)),
    ((2, 4), (-2, 41)),
    # seen negative — a signed quantity: the same codes split evenly.  The
    # transfer specs' `alice`, which counts DOWN: walks that saw [-3, 4]
    # packed it for [-10, 77] and desk-constraint-4p reaches -12 (ISSUE 51)
    ((-3, 4), (-43, 44)),
    ((-1, 4), (-30, 33)),
    ((-20, -10), (-76, 47)),
])
def test_an_observed_lane_seen_negative_gets_its_margin_on_both_sides(
        seen, packed_for):
    from jaxmc.compile.pack import LanePlan, _LaneClass
    lanes = [_LaneClass(None, None, True, False, False)] * 2
    plan = LanePlan(2, lanes, np.array([seen[0], 0]),
                    np.array([seen[1], 1000]), np.array([True, True]))
    lo = int(plan.bias[0])
    assert (lo, lo + int(plan.allowed[0])) == packed_for
    # free in bits: the old one-sided rule spent as many
    span = max(seen[1] - seen[0], 4)
    assert int(plan.allowed[0]) + 1 == (seen[1] - seen[0] + 2 * span + 1) * 4
    edge = np.array([[packed_for[0], 0], [packed_for[1], 0]], np.int32)
    assert (plan.unpack_np(plan.pack_np(edge)) == edge).all()


# ---------------------------------------------------------------- layer 3

def _device_counts(name, mode, env):
    from jaxmc.backend.bfs import TpuExplorer
    kw = dict(store_trace=mode != "resident")
    if mode == "resident":
        kw["resident"] = True
        kw["cap_profile"] = False
    elif mode == "host_seen":
        kw["host_seen"] = True
    for k, v in env.items():
        os.environ[k] = v
    try:
        ex = TpuExplorer(load(name), **kw)
        r = ex.run()
    finally:
        for k in env:
            os.environ.pop(k, None)
    return r


@pytest.mark.parametrize("name", sorted(FIXTURES))
@pytest.mark.parametrize("mode", ["level", "resident", "host_seen"])
def test_packed_vs_unpacked_vs_interp_counts(name, mode):
    from jaxmc import native_store
    from jaxmc.compile.vspec import ModeError
    if mode == "host_seen" and not native_store.is_available():
        pytest.skip("host_seen needs the native store")
    ri = Explorer(load(name)).run()
    try:
        rp = _device_counts(name, mode, {})
        ru = _device_counts(name, mode, {"JAXMC_PACK": "0"})
    except ModeError as e:
        if name == "interparm_toy" and mode in ("level", "resident"):
            pytest.skip(f"hybrid model needs host_seen: {e}")
        raise
    for tag, r in (("packed", rp), ("unpacked", ru)):
        assert (r.generated, r.distinct, r.ok) == \
            (ri.generated, ri.distinct, ri.ok), \
            (f"{name}/{mode}/{tag}: {r.generated}/{r.distinct}/{r.ok} "
             f"vs interp {ri.generated}/{ri.distinct}/{ri.ok}")


def _trace_states(violation):
    return [st for st, _lbl in violation.trace]


def test_trace_parity_packed_vs_unpacked_vs_interp():
    """Counterexample TRACES agree: pcal_intro_buggy's assert violation
    (repo-local, jax='yes' in the manifest).  Packed and unpacked
    device layouts must produce the IDENTICAL trace (bit-identical
    dedup partition); against the interpreter the trace must be an
    equally-short counterexample with identical counts (the two engines
    legitimately tie-break equal-depth candidates differently — a
    pre-existing, disclosed difference independent of packing)."""
    from jaxmc.backend.bfs import TpuExplorer
    spec = os.path.join(SPECS, "pcal_intro_buggy.tla")
    from jaxmc.front.cfg import ModelConfig

    def mk():
        m = Loader([SPECS]).load_path(spec)
        return bind_model(m, ModelConfig(specification="Spec"))

    ri = Explorer(mk()).run()
    assert not ri.ok and ri.violation.kind == "assert"
    runs = {}
    for tag, env in (("packed", {}), ("unpacked", {"JAXMC_PACK": "0"})):
        for k, v in env.items():
            os.environ[k] = v
        try:
            r = TpuExplorer(mk(), store_trace=True).run()
        finally:
            for k in env:
                os.environ.pop(k, None)
        assert not r.ok and r.violation.kind == "assert"
        runs[tag] = r
    assert _trace_states(runs["packed"].violation) == \
        _trace_states(runs["unpacked"].violation), \
        "packing changed the counterexample"
    assert len(_trace_states(runs["packed"].violation)) == \
        len(_trace_states(ri.violation)), \
        "device trace is not an equally-short counterexample"
    # counts at a violation abort reflect engine-specific partial-level
    # progress (the interp stops mid-enumeration, the device finishes
    # its batch) — only packed-vs-unpacked equality is meaningful here
    assert (runs["packed"].generated, runs["packed"].distinct) == \
        (runs["unpacked"].generated, runs["unpacked"].distinct)


def test_symmetry_composes_with_view(tmp_path):
    """SYMMETRY + VIEW together: the view must evaluate over the
    orbit's CANONICAL representative (the interp's state_fingerprint
    order), or symmetric states count as distinct — the review repro
    that caught the original view-of-raw-row keying."""
    from jaxmc.backend.bfs import TpuExplorer
    spec = tmp_path / "symview.tla"
    spec.write_text("""---- MODULE symview ----
EXTENDS Naturals, FiniteSets, TLC
CONSTANTS P, None
VARIABLES owner, cnt
Perms == Permutations(P)
Init == owner = None /\\ cnt = 0
Grab == \\E p \\in P : owner = None /\\ owner' = p /\\ cnt' = (cnt + 1) % 3
Drop == owner /= None /\\ owner' = None /\\ cnt' = cnt
Next == Grab \\/ Drop
Spec == Init /\\ [][Next]_<<owner, cnt>>
V == <<owner, cnt>>
====
""")
    cfg = parse_cfg("SPECIFICATION Spec\nCONSTANTS\n  P = {p1, p2}\n"
                    "  None = None\nSYMMETRY Perms\nVIEW V\n"
                    "CHECK_DEADLOCK FALSE\n")

    def mk():
        return bind_model(Loader([str(tmp_path)]).load_path(str(spec)),
                          cfg)

    ri = Explorer(mk()).run()
    ex = TpuExplorer(mk(), store_trace=True)
    assert ex.canon_fn is not None and ex.view_fn is not None
    r = ex.run()
    assert (r.generated, r.distinct, r.ok) == \
        (ri.generated, ri.distinct, ri.ok), \
        (f"SYMMETRY+VIEW diverged: device {r.generated}/{r.distinct} "
         f"vs interp {ri.generated}/{ri.distinct}")


@pytest.mark.parametrize("exchange", ["gather", "a2a"])
def test_mesh_packed_rows_survive_sharded_path(exchange):
    """Packed rows survive the mesh path (ISSUE 6): the sharded engine
    exchanges PACKED candidate rows (a2a payloads shrink to K+PW+1
    words) and still produces interp-identical counts — repo-local, so
    the sharded path stays covered without the reference tree."""
    from jaxmc.backend.mesh import MeshExplorer
    ri = Explorer(load("constoy")).run()
    me = MeshExplorer(load("constoy"), exchange=exchange,
                      store_trace=True)
    assert me.PW < me.W, "constoy must actually pack"
    r = me.run()
    assert (r.generated, r.distinct, r.ok) == \
        (ri.generated, ri.distinct, ri.ok)


def test_symtoy_trace_parity_on_violation():
    """symtoy's deadlock-with-checking-on violation: packed and
    unpacked device traces match the interpreter's (SYMMETRY canonical
    keys, original stored rows)."""
    from jaxmc.backend.bfs import TpuExplorer

    def mk():
        m = bind_model(
            Loader([SPECS]).load_path(os.path.join(SPECS, "symtoy.tla")),
            parse_cfg(open(os.path.join(SPECS, "symtoy.cfg")).read()))
        return m  # deadlock checking ON: the model deadlocks

    ri = Explorer(mk()).run()
    assert not ri.ok and ri.violation.kind == "deadlock"
    for env in ({}, {"JAXMC_PACK": "0"}):
        for k, v in env.items():
            os.environ[k] = v
        try:
            r = TpuExplorer(mk(), store_trace=True).run()
        finally:
            for k in env:
                os.environ.pop(k, None)
        assert not r.ok and r.violation.kind == "deadlock"
        assert _trace_states(r.violation) == _trace_states(ri.violation)
        assert (r.generated, r.distinct) == (ri.generated, ri.distinct)
