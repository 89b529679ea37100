r"""Differential tests: compiled kernels vs the exact interpreter.

For sampled states, the set of successors produced by the compiled action
kernels (decoded back to values) must equal the interpreter's successor set
— the per-transition equivalence underlying the whole-run count equality
(BASELINE.json).
"""

import os

import numpy as np
import pytest

from jaxmc.front.cfg import ModelConfig, parse_cfg
from jaxmc.sem.modules import Loader, bind_model
from jaxmc.sem.enumerate import enumerate_init, enumerate_next
from jaxmc.engine.explore import Explorer

from conftest import REFERENCE, needs_reference

SPECS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "specs")


def state_key(st, vars):
    return tuple(repr(st[v]) for v in vars)


def kernel_successors(ex, st):
    """Successor states via the compiled kernels for one concrete state
    (slotted kernels evaluated per slot index; kernels jitted once,
    cached on the action object so recycled ids cannot alias)."""
    import jax
    row = ex.layout.encode(st)
    out = set()
    overflow = False
    for ca in ex.compiled:
        jf = getattr(ca, "_jitted", None)
        if jf is None:
            jf = jax.jit(ca.fn)
            ca._jitted = jf
        slots = range(ca.n_slots) if ca.n_slots else [None]
        for k in slots:
            en, aok, ov, succ = (jf(row, k) if k is not None else jf(row))
            if bool(ov):
                overflow = True
            if bool(en):
                dec = ex.layout.decode(np.asarray(succ))
                out.add(state_key(dec, ex.layout.vars))
    return out, overflow


def interp_successors(model, st):
    ctx = model.ctx()
    out = set()
    for succ, _ in enumerate_next(model.next, ctx, model.vars, st):
        out.add(state_key(succ, model.vars))
    return out


@pytest.mark.parametrize("specrel,cfgrel", [
    ("specs/transfer_scaled.tla", "specs/transfer_scaled.cfg"),
])
def test_kernel_matches_interp_transfer(specrel, cfgrel):
    from jaxmc.backend.bfs import TpuExplorer
    root = os.path.dirname(SPECS)
    model = bind_model(
        Loader([]).load_path(os.path.join(root, specrel)),
        parse_cfg(open(os.path.join(root, cfgrel)).read()))
    ex = TpuExplorer(model, store_trace=False)
    ctx = model.ctx()
    states = enumerate_init(model.init, ctx, model.vars)[:6]
    # a couple of deeper states too
    for st in list(states[:2]):
        for succ, _ in enumerate_next(model.next, ctx, model.vars, st):
            states.append(succ)
            break
    for st in states:
        ks, ov = kernel_successors(ex, st)
        assert not ov
        assert ks == interp_successors(model, st)


@pytest.mark.parametrize("name", ["viewtoy_scaled", "symtoy_scaled"])
def test_resident_kernel_counts_equal_the_pins_at_bench_scale(name):
    """cfg VIEW and cfg SYMMETRY at bench scale: the packed resident kernel,
    at the manifest's own capacity record, counts what the manifest pins —
    and the interpreter meets the same pins in
    `tests/test_corpus.py::test_corpus_case[<name>.cfg]`, so the two engines
    are bit-identical on the whole run, not only per transition.  A second
    search on the warm engine repeats the answer.  (The plain wide rung,
    transfer_scaled: `tests/test_spans.py::...::
    test_transfer_scaled_meets_its_pins`.)"""
    from jaxmc.backend.bfs import TpuExplorer
    from jaxmc.corpus import case_for_cfg
    case = case_for_cfg(name + ".cfg")
    cfg = parse_cfg(open(case.cfg_path()).read())
    cfg.check_deadlock = not case.no_deadlock
    model = bind_model(Loader([SPECS]).load_path(case.spec_path()), cfg)
    caps = dict(case.res_caps)
    ex = TpuExplorer(model, store_trace=False, resident=True,
                     cap_profile=False, chunk=caps.pop("chunk"),
                     res_caps=caps)
    assert not ex.plan.identity, "the rung is there for the PACKED lanes"
    for _ in range(2):
        r = ex.run()
        assert (r.ok, r.truncated) == (True, False)
        assert (r.generated, r.distinct) == \
            (case.generated, case.distinct)


@pytest.mark.slow
def test_kernel_matches_interp_raft_tiny():
    from jaxmc.backend.bfs import TpuExplorer
    from jaxmc.compile.vspec import Bounds
    root = os.path.dirname(SPECS)
    ldr = Loader([os.path.join(REFERENCE, "examples")])
    model = bind_model(
        ldr.load_path(os.path.join(SPECS, "MCraft.tla")),
        parse_cfg(open(os.path.join(SPECS, "MCraft_tiny.cfg")).read()))
    ex = TpuExplorer(model, store_trace=False,
                     bounds=Bounds(seq_cap=2, grow_cap=16, kv_cap=16),
                     sample_cfg=(300, 60, 80))
    from jaxmc.engine.simulate import sample_states
    states = sample_states(model, bfs_states=40, n_walks=6, walk_depth=30)
    for st in states[:25]:
        ks, ov = kernel_successors(ex, st)
        assert not ov, "capacity overflow on sampled state"
        assert ks == interp_successors(model, st)


def test_nested_dynamic_exists_rejected(tmp_path):
    # two dynamic \E binders would share the one traced slot index and
    # silently explore only diagonal (i == j) pairs — the compiler must
    # reject instead (exactness contract: compile exactly or not at all)
    from jaxmc.compile.ground import CompileError
    from jaxmc.backend.bfs import TpuExplorer
    spec = tmp_path / "nested_dyn.tla"
    spec.write_text(r"""---- MODULE nested_dyn ----
EXTENDS Naturals, Sequences
VARIABLE q
Init == q = <<1, 2>>
Next == \E i \in 1..Len(q) : \E j \in 1..Len(q) :
          q' = [q EXCEPT ![i] = ((q[j] + 1) % 3)]
====
""")
    model = bind_model(Loader([]).load_path(str(spec)),
                       ModelConfig(init="Init", next="Next",
                                   check_deadlock=False))
    with pytest.raises(CompileError, match="nested dynamic"):
        TpuExplorer(model, store_trace=False)


def test_sibling_dynamic_exists_rejected(tmp_path):
    # /\-conjoined sibling dynamic \E binders also land in one grounded
    # action with distinct $slotv markers — same diagonal-only hazard as
    # the nested form, caught at action-compile time
    from jaxmc.compile.ground import CompileError
    from jaxmc.backend.bfs import TpuExplorer
    spec = tmp_path / "sibling_dyn.tla"
    spec.write_text(r"""---- MODULE sibling_dyn ----
EXTENDS Naturals, Sequences
VARIABLE q
Init == q = <<1, 2>>
Next == (\E i \in 1..Len(q) : q[i] < 9)
        /\ (\E j \in 1..Len(q) : q' = [q EXCEPT ![j] = ((q[j] + 1) % 3)])
====
""")
    model = bind_model(Loader([]).load_path(str(spec)),
                       ModelConfig(init="Init", next="Next",
                                   check_deadlock=False))
    with pytest.raises(CompileError, match="dynamic"):
        TpuExplorer(model, store_trace=False)


def _load_micro():
    ldr = Loader([os.path.join(REFERENCE, "examples"), SPECS])
    return bind_model(
        ldr.load_path(os.path.join(SPECS, "MCraftMicro.tla")),
        parse_cfg(open(os.path.join(SPECS, "MCraft_micro.cfg")).read()))


@needs_reference
def test_raft_micro_differential_default():
    # default-selected fast slice of the raft kernel-vs-interp
    # differential (the full sweep on MCraft_tiny is slow-marked above)
    from jaxmc.backend.bfs import TpuExplorer
    from jaxmc.engine.simulate import sample_states
    model = _load_micro()
    ex = TpuExplorer(model, store_trace=False)
    states = sample_states(model, bfs_states=30, n_walks=4, walk_depth=20)
    assert len(states) >= 12
    for st in states[:12]:
        ks, ov = kernel_successors(ex, st)
        assert not ov, "capacity overflow on sampled state"
        assert ks == interp_successors(model, st)


@needs_reference
def test_raft_micro_whole_run_equivalence():
    # the BASELINE.json contract at a scale that COMPLETES: identical
    # generated/distinct counts from the interpreter and the jax backend
    # on a raft model (MCraftMicro bounds raft.tla's message-bag domain so
    # the search is finite; reference hot path raft.tla:482-493)
    from jaxmc.backend.bfs import TpuExplorer
    from jaxmc import native_store
    ri = Explorer(_load_micro()).run()
    assert ri.ok
    assert (ri.generated, ri.distinct) == (6185, 694)
    rj = TpuExplorer(_load_micro(), store_trace=False,
                     host_seen=native_store.is_available(),
                     chunk=256).run()
    assert rj.ok
    assert (rj.generated, rj.distinct) == (6185, 694)


@pytest.mark.slow
def test_raft_3s_bench_whole_run_equivalence():
    # backend count-equivalence on the BENCHMARK model itself (bench.py's
    # workload): ~3.5min interp + ~6min jax on CPU
    from jaxmc.backend.bfs import TpuExplorer
    from jaxmc import native_store

    def load_bench():
        ldr = Loader([os.path.join(REFERENCE, "examples"), SPECS])
        return bind_model(
            ldr.load_path(os.path.join(SPECS, "MCraftMicro.tla")),
            parse_cfg(open(os.path.join(SPECS,
                                        "MCraft_3s_bench.cfg")).read()))
    ri = Explorer(load_bench()).run()
    assert ri.ok
    assert (ri.generated, ri.distinct) == (1138651, 76654)
    rj = TpuExplorer(load_bench(), store_trace=False,
                     host_seen=native_store.is_available()).run()
    assert rj.ok
    assert (rj.generated, rj.distinct) == (1138651, 76654)


def test_recursive_operator_demotes_predicate_with_named_reason(tmp_path):
    # ISSUE 5: a diverging RECURSIVE operator used to surface as an
    # anonymous RecursionError; the kernel2 unroll counter now trips
    # first and the demotion reason NAMES the operator. Invariants are
    # strict frames (no guard-demotion recovery), so the predicate must
    # land in fb_invs with that reason — while the non-recursive action
    # arm still compiles.
    from jaxmc.backend.bfs import TpuExplorer
    from jaxmc import native_store
    if not native_store.is_available():
        pytest.skip("hybrid (demoted invariant) needs the native store")
    (tmp_path / "rec.tla").write_text(
        "---------------- MODULE rec ----------------\n"
        "EXTENDS Naturals\n"
        "VARIABLES x\n"
        "RECURSIVE Depth(_)\n"
        "Depth(k) == IF k <= 0 THEN 0 ELSE 1 + Depth(k - 1)\n"
        "Init == x = 0\n"
        "Next == x < 4 /\\ x' = x + 1\n"
        "Spec == Init /\\ [][Next]_x\n"
        "RecInv == Depth(x) <= 4\n"
        "=============================================\n")
    cfg = parse_cfg("SPECIFICATION Spec\nINVARIANT RecInv\n"
                    "CHECK_DEADLOCK FALSE\n")
    model = bind_model(
        Loader([str(tmp_path)]).load_path(str(tmp_path / "rec.tla")),
        cfg)
    ex = TpuExplorer(model, store_trace=False,
                     host_seen=native_store.is_available())
    assert not ex.fb_arms, "the plain arm must stay compiled"
    assert len(ex.fb_invs) == 1
    nm, _e, reason = ex.fb_invs[0]
    assert nm == "RecInv"
    assert "recursive operator Depth exceeds the compile-time unroll " \
           "limit" in reason
    # and the hybrid run still produces exact counts
    r = ex.run()
    assert r.ok and (r.generated, r.distinct) == (5, 5)
