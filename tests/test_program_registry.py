r"""The program registry (ISSUE 37): a new engine dispatches the program
its process already holds.

An edit that leaves the model unchanged (a comment line, whitespace, the
cfg's constants in another order) is a new content hash, so a new session
and a new engine.  Its jitted programs used to be new `jax.jit` objects,
traced, lowered and loaded again; now an engine whose own cache misses asks
`compile/cache.py`'s registry under `TpuExplorer._program_sig()`, a
signature of everything the trace reads.  A wrong hit would answer one spec
with another's program, so what is held here, at toy size on XLA:CPU, one
parametrised case each:

  (a) the same model in new bytes hits, with the first engine's callable
      (`is`), no new executable, no XLA compile, the first's bytes under
      origin "held", and the first's counts;
  (b) ANY semantic edit — model, cfg, options, environment — misses, with
      the registry warm from the unedited model, and gives its own exact
      answer (the interpreter's);
  (c) equal signatures mean byte-identical lowered text of the engines' OWN
      programs: the property the registry rests on;
  (d) it fails closed: a hybrid engine, or a definition the walker cannot
      render, has no signature and runs as before;
  (e) it is bounded, an evicted program is traced again and answers right,
      and two threads asking for one key get one callable.
"""

import gc
import os
import threading
import weakref
from collections import OrderedDict

import jax
import jax.numpy as jnp
import pytest

from jaxmc import obs
from jaxmc.backend.bfs import TpuExplorer
from jaxmc.compile import cache
from jaxmc.engine.explore import Explorer
from jaxmc.session import CheckSession, SessionConfig, load_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = os.path.join(REPO, "specs")

#: bench/specs/transfer_scaled.tla with three things to edit: a definition
#: reached only through another (`Amount`), one of an EXTENDed module on the
#: include path (`Step`, Limits.tla), and a predicate the cfg may name
#: (`Small`)
SPEC = r"""------------------------- MODULE regtoy -------------------------
EXTENDS Naturals, Limits

CONSTANTS Procs, MaxMoney

VARIABLES alice, bob, money, pc

vars == <<alice, bob, money, pc>>

Init == /\ alice = MaxMoney
        /\ bob = 0
        /\ money \in [Procs -> 1..MaxMoney]
        /\ pc = [p \in Procs |-> "check"]

Amount(p) == money[p]

Check(p) == /\ pc[p] = "check"
            /\ pc' = [pc EXCEPT ![p] =
                         IF alice >= money[p] THEN "debit" ELSE "done"]
            /\ UNCHANGED <<alice, bob, money>>

Debit(p) == /\ pc[p] = "debit"
            /\ alice' = alice - Amount(p)
            /\ pc' = [pc EXCEPT ![p] = "credit"]
            /\ UNCHANGED <<bob, money>>

Credit(p) == /\ pc[p] = "credit"
             /\ bob' = bob + Step(money[p])
             /\ pc' = [pc EXCEPT ![p] = "done"]
             /\ UNCHANGED <<alice, money>>

Terminating == /\ \A p \in Procs : pc[p] = "done"
               /\ UNCHANGED vars

Next == (\E p \in Procs : Check(p) \/ Debit(p) \/ Credit(p)) \/ Terminating

Spec == Init /\ [][Next]_vars

AliceBounded == alice <= MaxMoney

Small == bob <= 2
=============================================================================
"""
LIMITS = ("---- MODULE Limits ----\nEXTENDS Naturals\n"
          "Step(x) == x\n====\n")
CFG = ("SPECIFICATION Spec\nINVARIANT AliceBounded\nCONSTANTS\n"
       "  Procs = {p1, p2}\n  MaxMoney = 3\n")
#: the level engine's warm run checks another model than the resident
#: engine's, so that moving either to the other engine finds nothing warm
CFG_LEVEL = CFG.replace("MaxMoney = 3", "MaxMoney = 2")
RESIDENT = {"resident": True, "no_trace": True}
#: the spec without its stuttering disjunct: every run ends in a deadlock
DEADLOCKS = SPEC.replace(" \\/ Terminating\n", "\n")


@pytest.fixture(autouse=True)
def _forget_programs():
    """Overrides conftest's per-test emptying: this module keeps the
    registry WARM from `warm` on, which is the point of (b)."""


@pytest.fixture(autouse=True, scope="module")
def _own_capacities():
    # for the module, `warm` included: the environment's JAXMC_* names
    # are part of the signature
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAXMC_CAP_PROFILE", "0")
        yield


class Run:
    """One model written to its own directory, checked on a new session
    under its own recorder."""

    def __init__(self, root, tag, spec=SPEC, cfg=CFG, limits=LIMITS,
                 opts=RESIDENT, no_deadlock=False):
        d = os.path.join(str(root), tag)
        inc = os.path.join(d, "include")
        os.makedirs(inc)
        self.spec = os.path.join(d, "regtoy.tla")
        self.cfg = os.path.join(d, "regtoy.cfg")
        for path, text in ((self.spec, spec), (self.cfg, cfg),
                           (os.path.join(inc, "Limits.tla"), limits)):
            with open(path, "w", encoding="utf-8") as fh:
                fh.write(text)
        self.include, self.no_deadlock = (inc,), no_deadlock
        self.tel = obs.Telemetry()
        with obs.use(self.tel):
            self.sess = CheckSession(SessionConfig(
                spec=self.spec, cfg=self.cfg, include=self.include,
                backend="jax", platform="cpu", no_deadlock=no_deadlock,
                **opts), tel=self.tel)
            self.result = self.sess.explore()
        self.engine = self.sess.engine
        self.sig = self.engine._program_sig()

    def answer(self, r=None):
        r = self.result if r is None else r
        v = r.violation
        if v is not None:
            # a search that stops at a violation stops where its engine
            # notices it: the verdict is compared, the counts are not
            return (r.ok, None, None, None, (v.kind, v.name))
        return (r.ok, r.generated, r.distinct, r.diameter, None)

    def exact(self):
        """The interpreter's answer for the same files."""
        return self.answer(Explorer(load_model(
            self.spec, self.cfg, self.no_deadlock, self.include)).run())

    def resident_program(self):
        (fn,) = self.engine._res_cache.values()
        return fn

    def hits(self):
        return self.tel.counters.get("compile.program_hits", 0)


@pytest.fixture(scope="module")
def warm(tmp_path_factory):
    """The registry warm from three unedited models: the toy on the
    resident engine, a smaller one on the level engine with traces, and
    the deadlocking spec."""
    cache.forget_programs()
    root = tmp_path_factory.mktemp("registry")
    runs = {"root": root,
            "resident": Run(root, "base"),
            "level": Run(root, "base-level", cfg=CFG_LEVEL, opts={}),
            "deadlocks": Run(root, "base-dead", spec=DEADLOCKS)}
    for name in ("resident", "level", "deadlocks"):
        r = runs[name]
        assert r.sig is not None and r.answer() == r.exact(), name
        assert r.hits() == 0 or name == "deadlocks"
    assert runs["resident"].answer() == (True, 256, 166, 6, None)
    assert runs["deadlocks"].answer()[0] is False
    yield runs
    cache.forget_programs()


# --------------------------------------- (a) the same model in new bytes

def _stamped(text):
    head, rest = text.split("\n", 1)
    return head + "\n\\* ci-a commit 7\n" + rest


SAME = {
    "stamped": {"spec": _stamped(SPEC)},
    "whitespace": {"spec": SPEC.replace("alice - Amount(p)",
                                        "alice   -   Amount( p )")
                   .replace("CONSTANTS Procs, MaxMoney",
                            "CONSTANTS   Procs,\n   MaxMoney   ")
                   .replace("\nCheck(p)", "\n\n\n(* a block\n comment *)"
                            "\nCheck(p)")},
    "cfg-reordered": {"cfg": ("CONSTANTS\n  MaxMoney = 3\n"
                              "  Procs = {p2, p1}\nINVARIANT AliceBounded"
                              "\nSPECIFICATION Spec\n")},
    "include-elsewhere": {"limits": "\\* another checkout\n" + LIMITS},
}


@pytest.mark.parametrize("edit", sorted(SAME))
def test_the_same_model_in_new_bytes_hits_and_answers_the_same(warm, edit):
    first = warm["resident"]
    held = first.resident_program()
    size = held.__wrapped__._cache_size()
    second = Run(warm["root"], "same-" + edit, **SAME[edit])
    assert second.sig == first.sig
    assert second.engine is not first.engine
    # the FIRST engine's callable, and jax made no executable for it
    assert second.resident_program() is held
    assert held.__wrapped__._cache_size() == size
    assert second.tel.counters.get("compile.xla_compiles", 0) == 0
    assert second.tel.counters["compile.xla_compile_s"] == 0.0
    assert second.hits() >= 2                  # host_keys and run
    assert "compile.program_misses" not in second.tel.counters
    # what a hit must still tell the records
    made = {p["site"]: p for p in first.tel.prof.programs}
    got = {p["site"]: p for p in second.tel.prof.programs}
    assert set(got) == {"bfs.host_keys", "bfs.resident_run"}
    for site, p in got.items():
        assert p["origin"] == "held" and p["xla_s"] == 0.0
        assert p["dispatches"] >= 1
        assert {k: v for k, v in p.items()
                if k.endswith("_bytes") or k == "key"} == \
            {k: v for k, v in made[site].items()
             if k.endswith("_bytes") or k == "key"}
    assert second.tel.gauges["program.hbm_bytes"] == \
        made["bfs.resident_run"]["hbm_bytes"]
    assert not any(lv.get("fresh_compile") for lv in second.tel.levels)
    assert second.answer() == first.answer() == second.exact()


# ------------------------------------------------ (b) any semantic edit

#: name -> (which warm run it edits, what it changes)
EDITS = {
    "plus-to-minus": ("resident", {"spec": SPEC.replace(
        "alice' = alice - Amount(p)", "alice' = alice + Amount(p)")}),
    "invariant-tightened": ("resident", {"spec": SPEC.replace(
        "AliceBounded == alice <= MaxMoney",
        "AliceBounded == alice >= MaxMoney - 1")}),
    "maxmoney-3-to-4": ("resident", {"cfg": CFG.replace(
        "MaxMoney = 3", "MaxMoney = 4")}),
    "procs-2-to-3": ("resident", {"cfg": CFG.replace(
        "{p1, p2}", "{p1, p2, p3}")}),
    "definition-behind-a-definition": ("resident", {"spec": SPEC.replace(
        "Amount(p) == money[p]", "Amount(p) == 1")}),
    "definition-in-an-extended-module": ("resident", {
        "limits": LIMITS.replace("Step(x) == x", "Step(x) == x + 1")}),
    "check-deadlock-off-in-cfg": ("deadlocks", {
        "spec": DEADLOCKS, "cfg": CFG + "CHECK_DEADLOCK FALSE\n"}),
    "check-deadlock-off-by-option": ("deadlocks", {
        "spec": DEADLOCKS, "no_deadlock": True}),
    "constraint-added": ("resident", {"cfg": CFG + "CONSTRAINT Small\n"}),
    "level-engine": ("resident", {"opts": {}}),
    "resident-engine": ("level", {"cfg": CFG_LEVEL, "opts": RESIDENT}),
    "no-trace": ("level", {"cfg": CFG_LEVEL, "opts": {"no_trace": True}}),
    "por": ("resident", {"opts": dict(RESIDENT, por=True)}),
    "chunk": ("resident", {"opts": dict(RESIDENT, chunk=128)}),
    "caps": ("resident", {"opts": dict(RESIDENT, res_caps={
        "SC": 1 << 16, "FCap": 4096, "AccCap": 1 << 15, "VC": 1 << 13})}),
    "unpacked-lanes": ("resident", {"env": {"JAXMC_PACK": "0"}}),
}


@pytest.mark.parametrize("edit", sorted(EDITS))
def test_a_semantic_edit_misses_and_gives_its_own_answer(
        warm, edit, monkeypatch):
    base_name, change = EDITS[edit]
    base = warm[base_name]
    change = dict(change)
    for name, value in change.pop("env", {}).items():
        monkeypatch.setenv(name, value)
    kw = {"opts": {} if base_name == "level" else RESIDENT}
    kw.update(change)
    run = Run(warm["root"], "edit-" + edit, **kw)
    held = {id(fn) for fn in cache._PROGRAMS.values()} - \
        {id(fn) for fn in run.engine._res_cache.values()}
    if edit == "caps":
        # the same trace inputs at other capacities: the SITE's key
        # differs, not the signature — the host-keys program may hit,
        # the search program never
        assert run.sig == base.sig
        assert tuple(run.tel.prof.programs[-1]["key"]) != \
            tuple(base.tel.prof.programs[-1]["key"])
    else:
        assert run.sig is not None and run.sig != base.sig
        mine, theirs = ({id(fn) for e in (r.engine,) for c in (
            e._res_cache, e._hostkeys_cache) for fn in c.values()}
            for r in (run, base))
        assert mine and theirs and not mine & theirs
        # (CHECK_DEADLOCK FALSE in the cfg is the model the case before
        # it made with the option: two ways to say one thing may share)
        assert run.hits() == 0 or edit == "check-deadlock-off-in-cfg"
    # whatever it dispatched for the search, no earlier engine made it
    for fn in run.engine._res_cache.values():
        assert all(fn is not other.resident_program()
                   for other in (warm["resident"], warm["deadlocks"]))
    search = [p for p in run.tel.prof.programs
              if p["site"] != "bfs.host_keys"]
    assert search and all(
        p["origin"] == "compiled" or edit == "check-deadlock-off-in-cfg"
        for p in search)
    assert held  # ... and the registry was warm while it did
    want = run.exact()
    if edit == "por":
        # a reduction keeps the verdict, not the raw counts
        assert run.answer()[0] == want[0] and run.answer()[4] == want[4]
    else:
        assert run.answer() == want
    if edit in ("plus-to-minus", "invariant-tightened"):
        assert run.answer()[0] is False        # never the held `ok`
        assert run.answer()[4] == ("invariant", "AliceBounded")
    if edit.startswith("check-deadlock-off"):
        assert base.answer()[4][0] == "deadlock"
        assert run.answer()[0] is True


# ------------------- (c) equal signatures, byte-identical lowered text

def _lowered(engine):
    """The lowered text of the engine's OWN programs: made anew from its
    own closures, whatever the registry holds."""
    (key,) = engine._res_cache
    SC, FCap = key[0], key[1]
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    run = engine._make_resident_run(*key).__wrapped__
    text = run.lower(
        jax.ShapeDtypeStruct((SC, engine.K), jnp.int32), i32,
        jax.ShapeDtypeStruct((FCap, engine.PW), jnp.int32),
        *([i32] * 7)).as_text()
    keys_of = engine._keys_fn()
    keys = jax.jit(lambda rows, valid: keys_of(rows, valid)).lower(
        jax.ShapeDtypeStruct((8, engine.W), jnp.int32),
        jax.ShapeDtypeStruct((8,), jnp.bool_)).as_text()
    return text, keys


@pytest.mark.parametrize("edit", sorted(SAME))
def test_equal_signatures_lower_to_identical_text(warm, edit):
    first = warm["resident"]
    second = Run(warm["root"], "text-" + edit, **SAME[edit])
    assert second.sig == first.sig
    a, b = _lowered(first.engine), _lowered(second.engine)
    assert a[0] == b[0] and a[1] == b[1]
    assert "stablehlo.while" in a[0]
    # ... and a semantic edit's differs (the walker is not why they hit)
    other = Run(warm["root"], "text-other-" + edit, cfg=CFG.replace(
        "MaxMoney = 3", "MaxMoney = 2"))
    assert other.sig != first.sig
    assert _lowered(other.engine)[0] != a[0]


# ------------------------------------------------- (d) it fails closed

def _unkeyed_engine(kind):
    if kind == "fallback-arm":
        model = load_model(os.path.join(SPECS, "interparm_toy.tla"),
                           os.path.join(SPECS, "interparm_toy.cfg"), False)
        return TpuExplorer(model, host_seen=True, store_trace=False), \
            (True, 29, 19)
    model = load_model(os.path.join(SPECS, "constoy.tla"),
                       os.path.join(SPECS, "constoy.cfg"), False)
    # a definition the walker cannot render: `repr` holds an address
    model.defs["Opaque"] = {"closure": lambda: None,
                            "object": object()}[kind]
    return TpuExplorer(model, resident=True, store_trace=False), \
        (True, 43, 21)


@pytest.mark.parametrize("kind", ["fallback-arm", "closure", "object"])
def test_what_cannot_be_signed_is_not_shared(warm, kind):
    before = dict(cache._PROGRAMS)
    tels = []
    for _ in range(2):
        tel = obs.Telemetry()
        with obs.use(tel):
            engine, want = _unkeyed_engine(kind)
            assert engine._program_sig() is None
            r = engine.run()
        assert (r.ok, r.generated, r.distinct) == want
        tels.append(tel)
    for tel in tels:                           # the second as the first
        assert tel.counters["compile.program_unkeyed"] >= 1
        assert "compile.program_hits" not in tel.counters
        assert "compile.program_misses" not in tel.counters
        assert all(p["origin"] == "compiled" for p in tel.prof.programs)
    assert dict(cache._PROGRAMS) == before     # nothing kept


def test_a_runtime_demotion_forgets_the_signature(warm):
    engine = Run(warm["root"], "demoted").engine
    assert engine._program_sig() == warm["resident"].sig
    engine._demote_arms([0])
    assert engine._program_sig() is None


def test_the_walker_renders_by_value_and_refuses_the_rest():
    from jaxmc.front import tla_ast as A
    from jaxmc.sem.values import Fcn, ModelValue
    same = [cache.canonical(v) for v in (
        frozenset({ModelValue("b"), ModelValue("a"), 3, "x"}),
        frozenset({"x", 3, ModelValue("a"), ModelValue("b")}))]
    assert same[0] == same[1]
    assert cache.canonical({"b": 1, "a": 2}) == \
        cache.canonical({"a": 2, "b": 1})
    assert cache.canonical(Fcn({1: "a", 2: "b"})) == \
        cache.canonical(Fcn({2: "b", 1: "a"}))
    node = A.OpApp("+", (A.Ident("x"), A.Num(1)))
    assert cache.canonical(node) == cache.canonical(
        A.OpApp("+", (A.Ident("x"), A.Num(1))))
    assert cache.canonical(node) != cache.canonical(
        A.OpApp("-", (A.Ident("x"), A.Num(1))))
    # what is hashed is the `repr`: True is not 1 there
    assert repr(cache.canonical((True,))) != repr(cache.canonical((1,)))
    assert cache.canonical((1, 2)) != cache.canonical(frozenset({1, 2}))
    loop = {}
    loop["self"] = loop
    for bad in (object(), lambda: None, 1.5 + 2j, loop, {"k": [object()]}):
        with pytest.raises(cache.Unrenderable):
            cache.canonical(bad)


# ------------------------------------------------------- (e) the bound

@pytest.fixture
def empty_registry(monkeypatch):
    """A registry of this test's own, the module's warm one untouched."""
    monkeypatch.setattr(cache, "_PROGRAMS", OrderedDict())
    return cache._PROGRAMS


def test_the_registry_never_exceeds_its_constant(empty_registry,
                                                 monkeypatch):
    monkeypatch.setattr(cache, "_PROGRAMS_MAX", 3)
    tel = obs.Telemetry()
    with obs.use(tel):
        made = [cache.held_program("site", "sig", k, object)
                for k in range(5)]
        assert len(empty_registry) == 3
        assert [k for _, _, k in empty_registry] == [2, 3, 4]
        # a hit makes its entry the newest: 2 outlives 3
        assert cache.held_program("site", "sig", 2, object) is made[2]
        cache.held_program("site", "sig", 5, object)
        assert [k for _, _, k in empty_registry] == [4, 2, 5]
        # no signature: nothing is kept, nothing is looked up
        assert cache.held_program("site", None, 2, object) is not made[2]
        assert len(empty_registry) == 3
    assert tel.counters["compile.program_misses"] == 6
    assert tel.counters["compile.program_hits"] == 1
    assert tel.counters["compile.program_unkeyed"] == 1
    assert isinstance(cache._PROGRAMS_MAX, int) and cache._PROGRAMS_MAX > 0


def test_an_evicted_program_is_traced_again_and_answers_right(
        empty_registry, monkeypatch, tmp_path):
    monkeypatch.setattr(cache, "_PROGRAMS_MAX", 1)
    first = Run(tmp_path, "one")               # host_keys evicted by run
    assert [site for site, _, _ in empty_registry] == ["bfs.resident_run"]
    second = Run(tmp_path, "two", spec=_stamped(SPEC))
    assert second.sig == first.sig
    origins = {p["site"]: p["origin"] for p in second.tel.prof.programs}
    assert origins["bfs.host_keys"] == "compiled"   # traced again
    # ... which evicted the search program in its turn
    assert origins["bfs.resident_run"] == "compiled"
    assert second.resident_program() is not first.resident_program()
    assert second.answer() == first.answer() == (True, 256, 166, 6, None)


def test_an_entry_pins_kernels_not_the_engine(empty_registry, tmp_path):
    """What the registry keeps alive: the jitted callables and what their
    traced closures hold (kernels, lane plan, the model they were built
    from) — not the engine that made them, with its init states, tables
    and caches (`_keys_fn` is a closure over four fields, not `self`)."""
    run = Run(tmp_path, "pinned")
    engine, model = weakref.ref(run.engine), weakref.ref(run.engine.model)
    held = run.resident_program()
    assert {site for site, _, _ in empty_registry} == \
        {"bfs.host_keys", "bfs.resident_run"}
    del run
    gc.collect()
    assert engine() is None
    assert model() is not None                 # the kernels' context
    assert held in empty_registry.values()
    cache.forget_programs()                    # this test's registry
    del held
    gc.collect()
    assert model() is None


def test_two_threads_asking_for_one_key_get_one_callable(empty_registry):
    made, got = [], []
    gate = threading.Barrier(8)

    def make():
        made.append(object())
        return made[-1]

    def ask():
        gate.wait()
        got.append(cache.held_program("site", "sig", "key", make))

    threads = [threading.Thread(target=ask) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(made) == 1 and len(got) == 8
    assert all(fn is made[0] for fn in got)
