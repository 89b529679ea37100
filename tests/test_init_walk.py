r"""A large Init is walked once (ISSUE 52): the engine enumerates Init, hands
the list to the layout sampler, takes the initial states' rows back from the
layout build and encodes nothing again in its first search.

The sampled list, the layout signature and the program signature are held to
GOLDENS taken from the parent of that change (commit cce3473): the change is
host work only, every lowered program must stay the parent's text.  To take
them again from a tree, with the suite's environment:

    PYTHONPATH=<tree> python3 tests/test_init_walk.py

prints the `GOLDEN` table of that tree (it uses nothing the parent lacks).
"""

import hashlib
import os
import sys

if __name__ == "__main__":  # the suite's environment (conftest.py), by hand
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")
    os.environ["JAX_PLATFORMS"] = "cpu"

import pytest

from jaxmc import obs
from jaxmc.sem.values import fmt
from jaxmc.session import CheckSession, SessionConfig, load_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench", "specs")

# The five bench specs at the cfgs their cells rehearse with
# (bench/traffic/*.json `rehearsal_cfg`, bench/specs/portoy_ok.cfg), and two
# specs in the corpus' style, written here because the corpus is not
# mounted where the suite is built: a channel of records in a sequence (a
# BOOLEAN in every record: the sampler's key must keep TRUE and 1 apart)
# and a bag of message records in a set.
CHAN = r"""
---------------------------- MODULE chan ----------------------------
EXTENDS Naturals, Sequences
CONSTANTS Data, Cap
VARIABLES q, sent, last

Init == /\ q = << >>
        /\ sent = 0
        /\ last = [d |-> 0, bit |-> FALSE]

Send == /\ Len(q) < Cap
        /\ sent < Cap + 1
        /\ \E d \in Data :
             q' = Append(q, [d |-> d, bit |-> (sent % 2 = 0)])
        /\ sent' = sent + 1
        /\ UNCHANGED last

Recv == /\ Len(q) > 0
        /\ last' = Head(q)
        /\ q' = Tail(q)
        /\ UNCHANGED sent

Next == Send \/ Recv
Spec == Init /\ [][Next]_<<q, sent, last>>
Short == Len(q) <= Cap
=====================================================================
"""
BAG = r"""
---------------------------- MODULE bag ----------------------------
EXTENDS Naturals
CONSTANTS Procs, Max
VARIABLES msgs, phase

Init == /\ msgs = {}
        /\ phase \in [Procs -> 0 .. 1]

Cast(p) == /\ phase[p] < Max
           /\ msgs' = msgs \cup {[from |-> p, n |-> phase[p]]}
           /\ phase' = [phase EXCEPT ![p] = @ + 1]

Next == \E p \in Procs : Cast(p)
Spec == Init /\ [][Next]_<<msgs, phase>>
Sane == \A m \in msgs : m.n < Max
====================================================================
"""
# a cfg CONSTRAINT that discards an initial state (x = 3 is generated and
# fingerprinted, never explored)
RING = r"""
---------------------------- MODULE ring ----------------------------
EXTENDS Naturals
VARIABLES x, y

Init == x \in 0 .. 3 /\ y = 0
Next == /\ y < 2
        /\ x' = (x + 1) % 4
        /\ y' = y + 1
Spec == Init /\ [][Next]_<<x, y>>
Low == x < 3
Small == y <= 2
=====================================================================
"""

_TRANSFER = "SPECIFICATION Spec\nINVARIANT AliceBounded\nCONSTANTS\n"
MODELS = {
    "scaled-3p5": ("transfer_scaled.tla", _TRANSFER +
                   "  Procs = {p1, p2, p3}\n  MaxMoney = 5\n"),
    "scaled-2p3": ("transfer_scaled.tla", _TRANSFER +
                   "  Procs = {p1, p2}\n  MaxMoney = 3\n"),
    "symmetry-3p3": ("transfer_symmetry.tla",
                     "SPECIFICATION Spec\nINVARIANT AliceBounded\n"
                     "SYMMETRY Perms\nCONSTANTS\n"
                     "  Procs = {p1, p2, p3}\n  MaxMoney = 3\n"),
    "retry-3p2": ("transfer_retry.tla",
                  "SPECIFICATION SpecR\nINVARIANT AliceBounded\n"
                  "CONSTRAINT TriesBounded\nCONSTANTS\n"
                  "  Procs = {p1, p2, p3}\n  MaxMoney = 2\n  MaxTries = 1\n"),
    "violation-2p3": ("transfer_violation.tla",
                      "SPECIFICATION Spec\n"
                      "INVARIANTS AliceBounded NoMoneyCreated\nCONSTANTS\n"
                      "  Procs = {p1, p2}\n  MaxMoney = 3\n"),
    "portoy-ok": ("portoy.tla", None),  # bench/specs/portoy_ok.cfg
    "chan": (CHAN, "SPECIFICATION Spec\nINVARIANT Short\nCONSTANTS\n"
                   "  Data = {1, 2}\n  Cap = 2\n"),
    "bag": (BAG, "SPECIFICATION Spec\nINVARIANT Sane\nCONSTANTS\n"
                 "  Procs = {p1, p2}\n  Max = 3\nCHECK_DEADLOCK FALSE\n"),
    "ring": (RING, "SPECIFICATION Spec\nINVARIANT Small\nCONSTRAINT Low\n"
                   "CHECK_DEADLOCK FALSE\n"),
}

# from the PARENT (cce3473), `python3 tests/test_init_walk.py` as above:
# the sampled list without repeats (how many, sha256 of the states'
# spellings in order),
# `_layout_sig()`, `_program_sig()` of the resident no-trace engine
GOLDEN = {'bag': {'layout_sig': 'ac48a289554c831f50396b1826b8ef7869ffcd57511f98828a8ad9987b60229b',
         'program_sig': '2d36895375ff18cb9de95bc3f38277239013d8827a2fdf309d130990be586b40',
         'samples': 49,
         'sha256': '8509739e9d2b47f5fddf99c7be7d60766f7681f3fcc98772c3de1e2c89bca9b8'},
 'chan': {'layout_sig': 'a4dda3a11f9c4f3c3f88148574e7739aba5680b1296cb42d444e2634970b6615',
          'program_sig': '0b91dcbe2bd57ccc77d62eda24d9e99ac45d056cfb8c6e83ed26449e0316a29d',
          'samples': 29,
          'sha256': '0979818b4e2752978947509ea513f08eb6422b355aab3e5f8648c67ceb227a99'},
 'portoy-ok': {'layout_sig': '8fa075e4f9a2ce2d39d3efec56538b6b190d0ebf2be1c6ed611b276c9111c75d',
               'program_sig': '2cecbd168227860511ac17b1ada82a9517556b9dec05f64fb47323615094361a',
               'samples': 150,
               'sha256': '297121137005d118d03f2a7f6a9c28fe4264c3ff1dcbf767a18917a3f8c52285'},
 'retry-3p2': {'layout_sig': '6c5f577ca43d21af5aec0a5cc4673231f19bce7ecbf624b3381d2cd3eeebf0bd',
               'program_sig': '63f2d6e4b219acd0488423917f5eef62046fff2997628d980019da10d6593749',
               'samples': 983,
               'sha256': '1199358baed4ac3be0716c481e29987ea130e4cadf5bc639525e4efb0497a824'},
 'ring': {'layout_sig': '5f717f775004fd329ef686efb7c8c5a59a43942d87d85d13f480a01812c5f569',
          'program_sig': 'f5801e7ee2cc6fa2b4eb4bd2ec10679e7981e97c5b7b0768ad2f28dcd23dc5ee',
          'samples': 7,
          'sha256': '2efff5f231b3efc3f533f0e23ab162718c6fe4cf4b00c3e856f0f8761a28e279'},
 'scaled-2p3': {'layout_sig': '9c6512d301fb9040b399120e0de9f5ba963d44bde6b5aee2bee6a248fd61d52b',
                'program_sig': 'a3412828ec34c4c4b1cae2343a4313ac421c7948a8bb9846025ab79a8d046c90',
                'samples': 166,
                'sha256': 'a63fda6f08092d855187d27f5671eaa0989477c48453f6e1143ce67855082260'},
 'scaled-3p5': {'layout_sig': '87432b2c6fa71ed4eedd30ce572d9fb8490ea2e12ae57f1ef49b6cd1c26833cb',
                'program_sig': '6a74efc5c251ef8c19bafb9746081767b0a8143398300e781a6759e5f4974bda',
                'samples': 1029,
                'sha256': '05be6c96f78595d30e40320e90e7e6b99f527bd4419d82914406601b70008401'},
 'symmetry-3p3': {'layout_sig': '625fc49ee1a8642eb8e88cebb27541ba3199eb53ef0eea374740a6204c7b496b',
                  'program_sig': 'd83f18805e89a51814731c90a988d09e786f3e361bad7c6f35ce5d281c6c1cb3',
                  'samples': 938,
                  'sha256': '9475ee2ff4b4efb5f51a7decfee6006b5fbae1f1439982d9fb6811fecac44c4f'},
 'violation-2p3': {'layout_sig': '9c6512d301fb9040b399120e0de9f5ba963d44bde6b5aee2bee6a248fd61d52b',
                   'program_sig': '69d6c45f205c9d15fc1b29571a64ab76d28b7ea18274b575ef063198963daaeb',
                   'samples': 166,
                   'sha256': 'a63fda6f08092d855187d27f5671eaa0989477c48453f6e1143ce67855082260'}}

SAMPLE = (800, 40, 60)  # SessionConfig.sample: what every engine asks for


def _paths(name, work):
    """(spec path, cfg path) of a model of MODELS, written under `work`
    where it is text."""
    spec, cfg = MODELS[name]
    os.makedirs(str(work), exist_ok=True)
    if spec.endswith(".tla"):
        spec_path = os.path.join(BENCH, spec)
    else:
        spec_path = os.path.join(str(work), f"{name}.tla")
        with open(spec_path, "w", encoding="utf-8") as fh:
            fh.write(spec)
    if cfg is None:
        return spec_path, os.path.join(BENCH, "portoy_ok.cfg")
    cfg_path = os.path.join(str(work), f"{name}.cfg")
    with open(cfg_path, "w", encoding="utf-8") as fh:
        fh.write(cfg)
    return spec_path, cfg_path


def _spelling(st):
    """A sampled state as TLC would print it, variable by variable: the
    parent's dedup key, but for `repr` of a SET, which is Python's and
    follows the set's iteration order (so the parent listed some states of
    `bag` twice: 51 or 79 entries for its 49 states, by PYTHONHASHSEED)."""
    return tuple(sorted((k, fmt(v)) for k, v in st.items()))


def _digest(states):
    return hashlib.sha256(
        "\n".join(repr(_spelling(st)) for st in states).encode()).hexdigest()


def _first_of_each(states):
    """`states` without the repeats of a spelling, in order."""
    first = {}
    for st in states:
        first.setdefault(_spelling(st), st)
    return list(first.values())


def _session(name, work, tel=None, **opts):
    spec, cfg = _paths(name, work)
    opts.setdefault("backend", "jax")
    opts.setdefault("platform", "cpu")
    tel = tel if tel is not None else obs.NullTelemetry()
    return CheckSession(SessionConfig(spec=spec, cfg=cfg, **opts), tel=tel)


def _sig_env(setenv, delenv):
    """`_program_sig()` reads every JAXMC_* variable: the suite's two."""
    for k in [k for k in os.environ if k.startswith("JAXMC_")]:
        delenv(k)
    setenv("JAXMC_COMPILE_CACHE", "off")
    setenv("JAXMC_LEDGER", "off")


def _measure(name, work):
    """What GOLDEN holds of one model, from the tree on sys.path."""
    from jaxmc.engine.simulate import sample_states
    spec, cfg = _paths(name, work)
    model = load_model(spec, cfg, False, [])
    states = _first_of_each(sample_states(model, *SAMPLE))
    sess = _session(name, work, resident=True, no_trace=True).compile()
    return {"samples": len(states), "sha256": _digest(states),
            "layout_sig": sess.engine._layout_sig(),
            "program_sig": sess.engine._program_sig()}


if __name__ == "__main__":
    import pprint
    import tempfile
    _sig_env(os.environ.__setitem__, os.environ.__delitem__)
    with tempfile.TemporaryDirectory() as work:
        pprint.pprint({name: _measure(name, work) for name in MODELS},
                      width=76)
    sys.exit(0)


import gc  # noqa: E402

import numpy as np  # noqa: E402

from jaxmc.engine.simulate import sample_states, state_key  # noqa: E402
from jaxmc.sem.enumerate import enumerate_init, enumerate_next  # noqa: E402
from jaxmc.sem.values import Fcn, ModelValue  # noqa: E402

pytest.importorskip("jax")


@pytest.fixture(autouse=True)
def _no_capacity_profiles(monkeypatch):
    monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")


def _model(name, work):
    spec, cfg = _paths(name, work)
    return load_model(spec, cfg, False, [])


def _answer(res):
    return (res.generated, res.distinct, res.diameter, res.ok,
            bool(res.truncated))


def _interp_answer(name, work):
    """The exact interpreter's counts: the judge of every device run."""
    return _answer(_session(name, work, backend="interp",
                            platform=None).explore())


def _count_walks(monkeypatch):
    """Count the calls of `enumerate_init`, through every module of jaxmc
    that holds the name."""
    # every holder imported BEFORE the patch: a module that imports the
    # name while it is patched would keep the wrapper for good
    import jaxmc.backend.batch  # noqa: F401
    import jaxmc.backend.mesh  # noqa: F401
    calls = []
    real = enumerate_init

    def counted(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    for mod in list(sys.modules.values()):
        if getattr(mod, "__name__", "").startswith("jaxmc") and \
                getattr(mod, "enumerate_init", None) is real:
            monkeypatch.setattr(mod, "enumerate_init", counted)
    return calls


# ------------------------------------------------- (a) one walk of Init

ENGINES = {
    "level": ("scaled-2p3", dict()),
    "resident": ("scaled-2p3", dict(resident=True, no_trace=True)),
    "resident-traces": ("scaled-2p3", dict(resident=True)),
    "seen-cap": ("scaled-2p3", dict(resident=True, no_trace=True,
                                    seen_cap=4096)),
    "symmetry": ("symmetry-3p3", dict(resident=True, no_trace=True)),
    "constraint-discards-an-init": ("ring", dict(resident=True,
                                                 no_trace=True)),
    "mesh-2-devices": ("scaled-2p3", dict(devices=2)),
}


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_init_is_walked_once_from_compile_to_first_search(
        engine, tmp_path, monkeypatch):
    name, opts = ENGINES[engine]
    want = _interp_answer(name, tmp_path)
    calls = _count_walks(monkeypatch)
    tel = obs.Telemetry()
    with obs.use(tel):
        sess = _session(name, tmp_path, tel, **opts)
        sess.compile()
        assert len(calls) == 1
        assert _answer(sess.explore()) == want
        assert len(calls) == 1
        # nor does a second search walk it
        assert _answer(sess.explore()) == want
    assert len(calls) == 1
    gauges = tel.metrics_snapshot()["gauges"]
    assert gauges["layout.init_enumerations"] == 1
    assert gauges["layout.init_rows_reused"] == 1.0
    tel.close()


def test_a_discarded_init_is_generated_not_explored(tmp_path):
    # `ring` at its cfg: four initial states, x = 3 breaks the CONSTRAINT
    res = _session("ring", tmp_path, resident=True, no_trace=True).explore()
    assert _answer(res) == _interp_answer("ring", tmp_path)
    assert (res.generated, res.distinct) == (9, 6)


# --------------------------------------- (b) the sampled list, (c) the key

@pytest.mark.parametrize("name", sorted(MODELS))
def test_sampled_list_is_the_parents(name, tmp_path):
    model = _model(name, tmp_path)
    inits = enumerate_init(model.init, model.ctx(), model.vars)
    handed = sample_states(model, *SAMPLE, inits=inits)
    assert handed[:len(inits)] == inits
    assert all(a is b for a, b in zip(handed, inits))
    # no state twice (the parent's `repr` of a set listed some of `bag`'s)
    assert len(_first_of_each(handed)) == len(handed)
    assert (len(handed), _digest(handed)) == \
        (GOLDEN[name]["samples"], GOLDEN[name]["sha256"])
    # and the sampler that walks Init itself lists the same
    own = sample_states(model, *SAMPLE)
    assert [_spelling(st) for st in own] == [_spelling(st) for st in handed]


@pytest.mark.parametrize("name", sorted(MODELS))
def test_value_key_is_equal_exactly_when_the_spelling_is(name, tmp_path):
    model = _model(name, tmp_path)
    vars = tuple(model.vars)
    states = sample_states(model, *SAMPLE)[:400]
    # their successors too: most are states of the list again, reached
    # over another edge and built anew
    ctx = model.ctx()
    states = states + [succ for st in states[:200] for succ, _ in
                       enumerate_next(model.next, ctx, model.vars, st)]
    by_key, by_spelling = {}, {}
    for st in states:
        by_key.setdefault(state_key(st, vars), set()).add(_spelling(st))
        by_spelling.setdefault(_spelling(st), set()).add(
            state_key(st, vars))
    assert len(states) > len(by_key), "no state met twice: nothing shown"
    assert all(len(v) == 1 for v in by_key.values())
    assert all(len(v) == 1 for v in by_spelling.values())


P1 = ModelValue("p1")
APART = {
    "scalar": (True, 1),
    "scalar-false": (False, 0),
    "in-a-set": (frozenset({True}), frozenset({1})),
    "in-a-sequence": (Fcn({1: True, 2: 0}), Fcn({1: 1, 2: 0})),
    "in-a-record-in-a-set": (frozenset({Fcn({"bit": False, "d": 2})}),
                             frozenset({Fcn({"bit": 0, "d": 2})})),
    "in-a-function-of-functions": (Fcn({P1: Fcn({1: True})}),
                                   Fcn({P1: Fcn({1: 1})})),
    "as-keys": (Fcn({False: 6, True: 5}), Fcn({0: 6, 1: 5})),
}


@pytest.mark.parametrize("case", sorted(APART))
def test_value_key_keeps_boolean_and_integer_apart(case):
    a, b = APART[case]
    assert a == b and hash(a) == hash(b), "Python merges the two"
    assert fmt(a) != fmt(b), "TLA+ does not"
    assert state_key({"x": a, "y": 7}, ("x", "y")) != \
        state_key({"x": b, "y": 7}, ("x", "y"))


def test_value_key_of_one_set_built_in_two_orders_is_one_key():
    recs = [Fcn({"from": ModelValue(f"p{i}"), "n": i}) for i in range(40)]
    a, b = frozenset(recs), frozenset(reversed(recs))
    assert state_key({"x": a}, ("x",)) == state_key({"x": b}, ("x",))
    both = frozenset({True}), frozenset({True, False} - {False})
    assert state_key({"x": both[0]}, ("x",)) == \
        state_key({"x": both[1]}, ("x",))


# ------------------------------------------------------ (d) signatures

@pytest.mark.parametrize("name", sorted(MODELS))
def test_layout_and_program_signatures_are_the_parents(name, tmp_path,
                                                       monkeypatch):
    _sig_env(monkeypatch.setenv, monkeypatch.delenv)
    sess = _session(name, tmp_path, resident=True, no_trace=True).compile()
    assert sess.engine._layout_sig() == GOLDEN[name]["layout_sig"]
    assert sess.engine._program_sig() == GOLDEN[name]["program_sig"]


# ------------------------------------------- (e) the rows, (g) the gauges

def _build(name, work, tel, **engine_kw):
    from jaxmc.backend.bfs import TpuExplorer
    with obs.use(tel):
        return TpuExplorer(_model(name, work), store_trace=False,
                           resident=True, cap_profile=False, **engine_kw)


@pytest.mark.parametrize("name", ["scaled-2p3", "symmetry-3p3", "chan",
                                  "bag", "ring"])
def test_the_build_hands_on_the_rows_the_search_would_encode(name,
                                                             tmp_path):
    tel = obs.Telemetry()
    ex = _build(name, tmp_path, tel)
    want = np.stack([ex.layout.encode(st) for st in ex.init_states])
    assert ex._init_rows_built.dtype == np.int32
    assert np.array_equal(ex._init_rows_built, want)
    assert ex._init_rows_built.base is None, "a view keeps every sample"
    with obs.use(tel):
        assert _answer(ex.run()) == _interp_answer(name, tmp_path)
    events = tel.recent_events()
    opened = [e for e in events if e.get("ev") == "span_open"
              and e["name"] == "init_enumerate"]
    closed = [e for e in events if e.get("ev") == "span"
              and e["name"] == "init_enumerate"]
    assert len(opened) == len(closed) == 1
    assert closed[0]["attrs"]["states"] == len(want)
    gauges = tel.metrics_snapshot()["gauges"]
    assert gauges["layout.init_enumerations"] == 1
    assert gauges["layout.init_rows_reused"] == 1.0
    # shapes made afresh: a handful, where the samples are hundreds (and
    # none for `ring`, whose variables are integers)
    assert (name == "ring") == (gauges["layout.infer_distinct"] == 0)
    assert gauges["layout.infer_distinct"] <= 40
    assert gauges["layout.samples"] == GOLDEN[name]["samples"]
    tel.close()


def test_init_enumerate_is_a_child_of_engine_build(tmp_path):
    tel = obs.Telemetry()
    with obs.use(tel):
        _session("scaled-2p3", tmp_path, tel, resident=True,
                 no_trace=True).compile()
    opened = [e for e in tel.recent_events() if e.get("ev") == "span_open"
              and e["name"] == "init_enumerate"]
    assert [e["parent"] for e in opened] == ["engine_build"]
    tel.close()


def _refuse_one_sample(monkeypatch, nth):
    """The layout build's encoder refuses its `nth` value, once."""
    from jaxmc.compile import kernel2
    from jaxmc.compile.vspec import CompileError
    real, calls = kernel2.vs_encode, []

    def refusing(v, spec, uni, out):
        calls.append(1)
        if len(calls) == nth:
            raise CompileError("refused for the test")
        return real(v, spec, uni, out)

    monkeypatch.setattr(kernel2, "vs_encode", refusing)


FALLBACKS = ["extra-samples", "a-refused-init", "a-refused-later-sample",
             "follower"]


@pytest.mark.parametrize("case", FALLBACKS)
def test_fallbacks_yield_the_same_counts(case, tmp_path, monkeypatch):
    name = "scaled-2p3"
    want = _interp_answer(name, tmp_path)
    tel = obs.Telemetry()
    reused = 1.0
    if case == "extra-samples":
        # a relayout's engine: the samples still begin with Init
        deep = sample_states(_model(name, tmp_path), 50, 4, 30)[-5:]
        ex = _build(name, tmp_path, tel, extra_samples=deep)
    elif case == "a-refused-init":
        _refuse_one_sample(monkeypatch, 5)
        ex = _build(name, tmp_path, tel)
        assert ex._init_rows_built is None
        reused = 0
    elif case == "a-refused-later-sample":
        model = _model(name, tmp_path)
        n_init = len(enumerate_init(model.init, model.ctx(), model.vars))
        _refuse_one_sample(monkeypatch, len(model.vars) * (n_init + 3))
        ex = _build(name, tmp_path, tel)
        assert len(ex._init_rows_built) == n_init
    else:
        from jaxmc.backend.bfs import TpuExplorer
        with obs.use(tel):
            donor = TpuExplorer(_model(name, tmp_path), store_trace=False,
                                host_seen=True, cap_profile=False)
            ex = TpuExplorer(_model(name, tmp_path), donor=donor,
                             store_trace=False)
        assert ex._init_rows_built is None
        reused = 0
    monkeypatch.undo()
    with obs.use(tel):
        assert _answer(ex.run()) == want
    gauges = tel.metrics_snapshot()["gauges"]
    assert gauges["layout.init_rows_reused"] == reused
    tel.close()


def test_an_init_the_layout_cannot_encode_still_raises(tmp_path,
                                                       monkeypatch):
    from jaxmc.compile.vspec import CompileError
    _refuse_one_sample(monkeypatch, 5)
    ex = _build("scaled-2p3", tmp_path, obs.NullTelemetry())
    monkeypatch.undo()
    st = ex.init_states[1]
    var = ex.layout.vars[0]
    st[var] = frozenset({"not", "what", "was", "sampled"})
    with pytest.raises(CompileError):
        ex._prepare_init(0.0, [])


def test_a_cohort_walks_each_member_once(tmp_path, monkeypatch):
    from jaxmc.backend.batch import BatchCheckEngine
    calls = _count_walks(monkeypatch)
    cfgs = []
    for i, money in enumerate((2, 3, 4)):
        cfg = tmp_path / f"m{i}.cfg"
        cfg.write_text(_TRANSFER + "  Procs = {p1, p2}\n"
                       f"  MaxMoney = {money}\n")
        cfgs.append(SessionConfig(
            spec=os.path.join(BENCH, "transfer_scaled.tla"), cfg=str(cfg),
            backend="jax", platform="cpu", host_seen=True, no_trace=True))
    members = BatchCheckEngine(cfgs).build().run()
    assert len(calls) == len(cfgs)
    for mem, c in zip(members, cfgs):
        assert mem.error is None
        solo = CheckSession(c, tel=obs.NullTelemetry()).explore()
        assert _answer(mem.result) == _answer(solo)


# ------------------------------------------ (f) the states are let go

def _live_states(vars):
    gc.collect()
    want = set(vars)
    return sum(1 for o in gc.get_objects()
               if type(o) is dict and len(o) == len(want)
               and o.keys() == want)


@pytest.mark.parametrize("engine", ["level", "resident", "symmetry",
                                    "mesh-2-devices"])
def test_no_interpreter_state_outlives_the_first_search(engine, tmp_path):
    name, opts = ENGINES[engine]
    sess = _session(name, tmp_path, **opts).compile()
    vars = tuple(sess.model.vars)
    held = _live_states(vars)
    assert held >= len(sess.engine.init_states) > 0
    before = held - len(sess.engine.init_states)
    sess.explore()
    assert sess.engine.init_states is None
    assert sess.engine._init_rows_built is None
    assert _live_states(vars) == before
