r"""The sharded engine on the normal path (ISSUE 26): `SessionConfig.devices`
/ `check --devices N` build `MeshExplorer` over the first N devices, on the
8 virtual CPU devices conftest.py asks for.

Against the benchmark's plain reference (`bench/reference/transfer_scaled.py`,
numpy BFS from the spec's text, imports nothing of jaxmc) and against the
one-chip resident engine; the tie of the share to the whole — the four seen
shards are disjoint, each key sits where `_owner_from_keys` says, and their
union is the one-chip seen set; the refusals; the spans, scopes and counters.
"""

import importlib.util
import os
import random
import re
import subprocess
import sys

import numpy as np
import pytest

from jaxmc import obs
from jaxmc.compile.vspec import ModeError
from jaxmc.engine.explore import format_trace
from jaxmc.session import CheckSession, SessionConfig

pytest.importorskip("jax")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = os.path.join(REPO, "specs")
TRANSFER = os.path.join(REPO, "bench", "specs", "transfer_scaled.tla")
SIZES = {"2p3": (2, 3), "3p4": (3, 4)}


@pytest.fixture(autouse=True)
def _no_capacity_profiles(monkeypatch):
    # capacities from the engines' own defaults, whatever ran before
    monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    return _load(os.path.join(REPO, "bench", "reference",
                              "transfer_scaled.py"), "plain_reference")


def _cfg_text(procs, max_money, seed):
    """The cfg as the benchmark's seed writes it (`lib.permute_cfg`)."""
    lib = _load(os.path.join(REPO, "bench", "lib.py"), "bench_lib")
    names = ", ".join(f"p{i + 1}" for i in range(procs))
    return lib.permute_cfg(
        "SPECIFICATION Spec\nINVARIANT AliceBounded\nCONSTANTS\n"
        f"  Procs = {{{names}}}\n  MaxMoney = {max_money}\n", seed)


def _session(spec, cfg, tel=None, **opts):
    opts.setdefault("backend", "jax")
    opts.setdefault("platform", "cpu")
    return CheckSession(SessionConfig(spec=spec, cfg=cfg, **opts),
                        tel=tel if tel is not None else obs.NullTelemetry())


def _answer(res):
    return (res.generated, res.distinct, res.diameter, res.ok,
            bool(res.truncated))


# ------------------------------------------------ against the reference

@pytest.mark.parametrize("seed", [3, 2 ** 31 + 77])
@pytest.mark.parametrize("size", sorted(SIZES))
def test_mesh_session_equals_reference_and_resident(size, seed, tmp_path,
                                                    reference):
    procs, max_money = SIZES[size]
    text = _cfg_text(procs, max_money, seed)
    cfg = tmp_path / "t.cfg"
    cfg.write_text(text)
    n, m, invs = reference.parse_cfg(text)
    assert (n, m, invs) == (procs, max_money, ["AliceBounded"])
    want = reference.explore(n, m)
    want = (want["generated"], want["distinct"], want["diameter"],
            want["ok"], False)
    mesh = _session(TRANSFER, str(cfg), devices=4)
    from jaxmc.backend.mesh import MeshExplorer
    mesh.compile()
    assert isinstance(mesh.engine, MeshExplorer) and mesh.engine.D == 4
    assert _answer(mesh.explore()) == want
    assert mesh.finished_on == "jax"
    res = _session(TRANSFER, str(cfg), resident=True, no_trace=True)
    assert _answer(res.explore()) == want
    # the deployment changes where the state lives, not the layout
    assert mesh.layout_sig == res.layout_sig


#: per-shard capacity records for the two bench-scale fixtures, as measured
#: at D=4 on virtual CPU devices (SC grows 256 -> 65536 over nine recompiles
#: without one); powers of two, good for D=2 as well
BENCH_RUNGS = {
    "viewtoy_scaled": {"SC": 1 << 16, "FC": 1 << 11, "TRL": 32,
                       "GAM16": 32, "MSL": 32},
    "symtoy_scaled": {"SC": 1 << 15, "FC": 1 << 11, "TRL": 32,
                      "GAM16": 32, "MSL": 32},
}


@pytest.mark.parametrize("devices", [2, 4])
@pytest.mark.parametrize("name", sorted(BENCH_RUNGS))
def test_view_and_symmetry_at_bench_scale_meet_the_pins(name, devices):
    """cfg VIEW and cfg SYMMETRY at bench scale on the sharded engine: the
    manifest's counts (the interpreter's:
    `tests/test_corpus.py::test_corpus_case`) at D=2 and D=4, and never
    more scalar-ring reads than levels — a sync per level or fewer, no row
    traffic in the level loop (the parity legs of the deleted mesh harness,
    ISSUE 43)."""
    from jaxmc.corpus import case_for_cfg
    case = case_for_cfg(name + ".cfg")
    tel = obs.Telemetry()
    with obs.use(tel):
        sess = _session(case.spec_path(), case.cfg_path(), tel,
                        devices=devices, no_trace=True,
                        no_deadlock=case.no_deadlock,
                        res_caps=dict(BENCH_RUNGS[name]))
        res = sess.explore()
    assert res.ok and not res.truncated
    assert (res.generated, res.distinct) == (case.generated, case.distinct)
    assert sess.engine.D == devices and sess.engine.exchange == "a2a"
    assert 1 <= tel.counters["mesh.host_syncs"] <= len(tel.levels)


def test_explore_again_is_a_warm_rerun(tmp_path):
    cfg = tmp_path / "t.cfg"
    cfg.write_text(_cfg_text(2, 3, 0))
    tel = obs.Telemetry()
    with obs.use(tel):
        sess = _session(TRANSFER, str(cfg), tel, devices=4)
        first = _answer(sess.explore())
        n_levels = len(tel.levels)
        site = tel.prof.sites["mesh.superstep"]
        disp0, comp0 = site.dispatches, site.recompiles
        for _ in range(2):
            assert _answer(sess.explore()) == first
    assert first[:2] == (256, 166)
    assert not any(lv.get("fresh_compile") for lv in tel.levels[n_levels:])
    assert site.recompiles == comp0
    # the learned levels-per-dispatch covers the whole search: ONE
    # superstep a warm search
    assert site.dispatches == disp0 + 2
    assert tel.counters["mesh.host_syncs"] == site.dispatches
    assert sess.explore_count == 3


def test_pinned_mesh_caps_compile_once(tmp_path):
    """`res_caps` is the one capacity field: with devices > 1 it carries
    the mesh profile's keys, per shard, and the first search already runs
    the one program of every later one."""
    cfg = tmp_path / "t.cfg"
    cfg.write_text(_cfg_text(2, 3, 1))
    caps = {"SC": 1 << 10, "FC": 256, "TRL": 16, "GAM16": 32, "MSL": 16,
            "VC": 256}
    tel = obs.Telemetry()
    with obs.use(tel):
        sess = _session(TRANSFER, str(cfg), tel, devices=4, res_caps=caps)
        for _ in range(2):
            assert _answer(sess.explore())[:2] == (256, 166)
    site = tel.prof.sites["mesh.superstep"]
    assert (site.dispatches, site.recompiles) == (2, 1)
    assert sum(1 for lv in tel.levels if lv.get("fresh_compile")) == 1
    # 7 levels a search, each sorting VC slots and rewriting SC seen rows
    # on each of the four shards
    assert tel.counters["search.slots_sorted"] == 2 * 7 * 4 * 256
    assert tel.counters["search.seen_slots"] == 2 * 7 * 4 * (1 << 10)
    assert tel.counters["search.rows_valid"] == 2 * (256 - 9)
    assert tel.counters["search.rows_new"] == 2 * (166 - 9)


def test_slots_probed_follows_each_shards_live_keys(tmp_path, monkeypatch):
    """The probe's loops are bounded per shard (no collective inside):
    evenly hashed, the shards' valid keys and seen counts differ a
    little; under the mesh_skew fault shard 0 holds every key and the
    other three search nothing.  Same answer either way, the one-chip
    engine's; and the counter is the blocks that held a key."""
    from jaxmc import faults
    from jaxmc.backend import bfs
    monkeypatch.setattr(bfs, "_PROBE_BLOCK_MIN", 4)
    cfg = tmp_path / "t.cfg"
    cfg.write_text(_cfg_text(2, 3, 4))
    caps = {"SC": 1 << 10, "FC": 256, "TRL": 16, "GAM16": 32, "MSL": 16,
            "VC": 256}
    qb = bfs._probe_block_rows(caps["VC"])
    assert qb == 4
    answers = []
    try:
        for skew in (False, True):
            if skew:
                monkeypatch.setenv("JAXMC_FAULTS", "mesh_skew")
                faults.reset_for_tests()
            tel = obs.Telemetry()
            with obs.use(tel):
                sess = _session(TRANSFER, str(cfg), tel, devices=4,
                                res_caps=caps)
                answers.append(_answer(sess.explore()))
            assert sess.engine._skew is skew
            gen = [lv["generated"] for lv in tel.levels]
            assert sum(gen) == 256 - 9 and len(gen) == 7
            probed = tel.counters["search.slots_probed"]
            # one shard holding every valid key of a level searches
            # ceil(valid / QB) blocks; four sharing them at most three more
            least = sum(-(-g // qb) for g in gen) * qb
            assert probed % qb == 0
            if skew:
                assert probed == least
            else:
                assert least <= probed <= least + 3 * qb * len(gen)
            assert probed < tel.counters["search.slots_sorted"] \
                == 7 * 4 * caps["VC"]
    finally:
        faults.reset_for_tests()
    res = _session(TRANSFER, str(cfg), resident=True, no_trace=True)
    assert answers[0] == answers[1] == _answer(res.explore())
    assert answers[0][:2] == (256, 166)


def test_slots_merged_follows_each_shards_live_rows(tmp_path, monkeypatch):
    """The merge's build is bounded per shard as the probe is: a shard
    rebuilds the blocks of ITS table that hold a live row after the
    level.  Under the mesh_skew fault shard 0 holds every row, so the
    counter is the one-chip sum over the levels of ceil(seen / B) x B;
    evenly hashed, four shards share the rows and at most three more
    blocks a level are begun."""
    from jaxmc import faults
    from jaxmc.backend import bfs
    monkeypatch.setattr(bfs, "_MERGE_BLOCK_ROWS", 16)
    cfg = tmp_path / "t.cfg"
    cfg.write_text(_cfg_text(2, 3, 4))
    caps = {"SC": 1 << 10, "FC": 256, "TRL": 16, "GAM16": 32, "MSL": 16,
            "VC": 256}
    B = bfs._merge_block_rows(caps["SC"])
    assert B == 16
    try:
        for skew in (False, True):
            if skew:
                monkeypatch.setenv("JAXMC_FAULTS", "mesh_skew")
                faults.reset_for_tests()
            tel = obs.Telemetry()
            with obs.use(tel):
                sess = _session(TRANSFER, str(cfg), tel, devices=4,
                                res_caps=caps)
                assert _answer(sess.explore())[:2] == (256, 166)
            seen = [lv["seen"] for lv in tel.levels]
            assert len(seen) == 7 and seen[-1] == 166
            merged = tel.counters["search.slots_merged"]
            least = sum(-(-n // B) for n in seen) * B
            assert merged % B == 0
            if skew:
                assert merged == least
            else:
                assert least <= merged <= least + 3 * B * len(seen)
            assert merged < tel.counters["search.seen_slots"] \
                == 7 * 4 * caps["SC"]
    finally:
        faults.reset_for_tests()


@pytest.mark.parametrize("no_trace", [False, True])
def test_seed_and_table_bytes_on_the_mesh(no_trace, tmp_path):
    """`search.seed_bytes` / `search.table_bytes` on four shards.  The
    gauge (ISSUE 30) is the [D, SC, K] seen shards and [D, FC, PW]
    frontier shards and, where traces are kept, the trace ring
    [D, TRL, FC, PW] + [D, TRL, FC]; nothing grows at these capacities.
    The counter (ISSUE 35) is the bytes of the HEADS handed to the
    devices, from which they fill those tables: the 9 init states' keys
    and packed rows as one [D, H, .] block each, H the fullest shard's
    count under `_owner_from_keys`; a fresh search's ring has no head."""
    import time
    cfg = tmp_path / "t.cfg"
    cfg.write_text(_cfg_text(2, 3, 4))
    caps = {"SC": 1 << 10, "FC": 256, "TRL": 16, "GAM16": 32, "MSL": 16,
            "VC": 256}
    tel = obs.Telemetry()
    with obs.use(tel):
        sess = _session(TRANSFER, str(cfg), tel, devices=4, res_caps=caps,
                        no_trace=no_trace)
        assert _answer(sess.explore())[:2] == (256, 166)
    ex = sess.engine
    K, PW = ex.K, ex.PW
    assert K == 5
    table = 4 * 4 * (caps["SC"] * K + caps["FC"] * PW)
    if not no_trace:
        table += 4 * 4 * caps["TRL"] * caps["FC"] * (PW + 1)
    assert tel.gauges["search.table_bytes"] == table
    init_rows, explored, n_init, _ = ex._prepare_init(time.time(), [])
    assert n_init == 9 == len(explored)
    H = int(np.bincount(ex._owner_from_keys(ex._host_keys(init_rows)[0]),
                        minlength=4).max())
    assert 3 <= H < 9
    assert tel.counters["search.seed_bytes"] == 4 * 4 * H * (K + PW)


def test_slots_merged_of_the_pinned_model_on_four_shards(monkeypatch):
    """The 3-process model the benchmark pins (`bench/pins`), on four
    virtual devices at capacities that hold it without regrowth: after
    each level the shards together hold what the pins' levels say, and
    `search.slots_merged` is, shard by shard, the blocks that hold those
    rows — between the one-chip count and three more blocks a level."""
    import json
    from jaxmc.backend import bfs
    with open(os.path.join(REPO, "bench", "pins",
                           "transfer_scaled.json")) as fh:
        pins = json.load(fh)
    new = [lv[2] for lv in pins["levels"]]
    have = pins["distinct"] - sum(new)
    seen2 = [have := have + n for n in new]
    monkeypatch.setattr(bfs, "_MERGE_BLOCK_ROWS", 1 << 12)
    caps = {"SC": 1 << 16, "FC": 1 << 14, "TRL": 16, "GAM16": 32,
            "MSL": 16, "VC": 1 << 15}
    B = bfs._merge_block_rows(caps["SC"])
    assert B == 1 << 12
    tel = obs.Telemetry()
    with obs.use(tel):
        sess = _session(os.path.join(SPECS, "transfer_scaled.tla"),
                        os.path.join(SPECS, "transfer_scaled.cfg"), tel,
                        devices=4, res_caps=caps)
        res = sess.explore()
    assert (res.generated, res.distinct) == (pins["generated"],
                                             pins["distinct"])
    assert [lv["seen"] for lv in tel.levels] == seen2
    c = tel.counters
    assert c["search.seen_slots"] == len(seen2) * 4 * caps["SC"]
    least = sum(-(-n // B) for n in seen2) * B
    assert least <= c["search.slots_merged"] <= least + 3 * B * len(seen2)
    assert c["search.slots_merged"] % B == 0


# ------------------------------------------------ a violation's trace

def test_violating_cfg_gives_the_level_engines_trace():
    spec, cfg = (os.path.join(SPECS, "portoy.tla"),
                 os.path.join(SPECS, "portoy_bad.cfg"))
    level = _session(spec, cfg).explore()
    mesh = _session(spec, cfg, devices=4).explore()
    assert level.violation.kind == mesh.violation.kind == "invariant"
    assert format_trace(mesh.violation) == format_trace(level.violation)
    assert (mesh.ok, mesh.diameter) == (level.ok, level.diameter)


_BADHC = """---- MODULE badhc ----
EXTENDS Naturals
VARIABLE hr
HCini == hr \\in 1..12
HCnxt == hr' = IF hr >= 11 THEN 1 ELSE hr + 2
HC == HCini /\\ [][HCnxt]_hr
Jump == hr' = IF hr = 12 THEN 1 ELSE hr + 1
JumpSpec == HCini /\\ [][Jump]_hr
====
"""


@pytest.fixture()
def badhc(tmp_path):
    (tmp_path / "badhc.tla").write_text(_BADHC)
    (tmp_path / "badhc.cfg").write_text(
        "SPECIFICATION HC\nPROPERTY JumpSpec\nCHECK_DEADLOCK FALSE\n")
    return str(tmp_path / "badhc.tla"), str(tmp_path / "badhc.cfg")


def test_a_refinement_property_is_checked_on_the_normal_path(badhc):
    res = _session(*badhc, devices=4).explore()
    assert not res.ok
    assert (res.violation.kind, res.violation.name) == \
        ("property", "JumpSpec")


# ------------------------------------------------ the share and the whole

def _final_seen(spec, cfg, path, **opts):
    from jaxmc.engine.ckpt import load_checkpoint
    sess = _session(spec, cfg, checkpoint=str(path), final_checkpoint=True,
                    **opts)
    res = sess.explore()
    _, ck = load_checkpoint(str(path), kind="device")
    return sess, res, ck


@pytest.mark.parametrize("size", sorted(SIZES))
def test_shards_are_disjoint_owned_and_add_up_to_the_one_chip_set(
        size, tmp_path):
    procs, max_money = SIZES[size]
    cfg = tmp_path / "t.cfg"
    cfg.write_text(_cfg_text(procs, max_money, 11))
    one, res1, ck1 = _final_seen(TRANSFER, str(cfg), tmp_path / "one.ck",
                                 resident=True, no_trace=True)
    mesh, res4, ck4 = _final_seen(TRANSFER, str(cfg), tmp_path / "mesh.ck",
                                  devices=4)
    assert _answer(res1) == _answer(res4)
    whole = np.asarray(ck1["seen"])                    # [distinct', K]
    assert (whole[:, 0] == 0).all()
    shards = [np.asarray(ck4["seen"][d][:int(ck4["seen_counts"][d])])
              for d in range(4)]
    assert ck4["D"] == 4 and all(len(s) for s in shards)
    as_set = lambda rows: {r.tobytes() for r in rows}  # noqa: E731
    sets = [as_set(s) for s in shards]
    # a key sits in one shard only, and once
    assert [len(x) for x in sets] == [len(s) for s in shards]
    for a in range(4):
        for b in range(a + 1, 4):
            assert not (sets[a] & sets[b]), (a, b)
    # ... in the shard THE ownership formula names
    for d, s in enumerate(shards):
        assert (mesh.engine._owner_from_keys(s) == d).all(), d
        # and each shard keeps the rank merge's invariant: sorted prefix
        order = np.lexsort(tuple(s[:, i] for i in reversed(range(s.shape[1]))))
        assert (order == np.arange(len(s))).all(), d
    # the union is the one-chip resident engine's seen set, row for row
    union = np.concatenate(shards)
    assert len(union) == len(whole) >= res1.distinct
    key = lambda rows: rows[np.lexsort(            # noqa: E731
        tuple(rows[:, i] for i in reversed(range(rows.shape[1]))))]
    assert (key(union) == key(whole)).all()


# ------------------------------------------------ refusals, by name

@pytest.mark.parametrize("opts,word", [
    ({"seen": "exact"}, "--seen exact"),
    ({"host_seen": True}, "--host-seen"),
    ({"resident": True}, "--resident"),
    ({"backend": "interp", "platform": None}, "--devices 4"),
])
def test_options_the_mesh_cannot_honour_are_refused(opts, word, tmp_path):
    cfg = tmp_path / "t.cfg"
    cfg.write_text(_cfg_text(2, 3, 0))
    sess = _session(TRANSFER, str(cfg), devices=4, **opts)
    with pytest.raises(ModeError, match=re.escape(word)):
        sess.compile()
    assert sess.engine is None


@pytest.mark.parametrize("opts,word", [
    ({"no_trace": True}, "--no-trace"),
    ({"resume": "/nonexistent.ck"}, "resume"),
])
def test_properties_refuse_what_the_host_loop_cannot_do(badhc, opts, word):
    sess = _session(*badhc, devices=4, **opts)
    with pytest.raises(ModeError, match=word):
        sess.compile()


def test_fewer_devices_than_asked_is_no_result(tmp_path):
    cfg = tmp_path / "t.cfg"
    cfg.write_text(_cfg_text(2, 3, 0))
    sess = _session(TRANSFER, str(cfg), devices=16)
    with pytest.raises(RuntimeError, match=r"--devices 16 asked, 8 visible"):
        sess.compile()
    assert sess.engine is None and sess.result is None


# ------------------------------------------------ one option surface

def test_devices_is_part_of_the_job_and_one_is_todays_engine(tmp_path):
    cfg = tmp_path / "t.cfg"
    cfg.write_text(_cfg_text(2, 3, 0))
    base = dict(spec=TRANSFER, cfg=str(cfg), backend="jax", platform="cpu")
    sig = lambda **kw: SessionConfig(**base, **kw).job_signature_fields()  # noqa: E731,E501
    assert "devices" not in sig() and "devices" not in sig(devices=1)
    assert sig() == sig(devices=1) != sig(devices=4)
    assert sig(devices=4)["devices"] == 4
    # a one-chip job's signature is the PARENT's, to the byte: the blob
    # serve/protocol.py hashes, taken from the commit before the option
    import hashlib
    import json
    one = SessionConfig(spec="s.tla", cfg="c.cfg", backend="jax",
                        platform="cpu", devices=1).job_signature_fields()
    assert hashlib.sha256(json.dumps(one, sort_keys=True).encode()) \
        .hexdigest()[:16] == "5930673ddb8ae48f"
    from jaxmc.backend.bfs import TpuExplorer
    from jaxmc.backend.mesh import MeshExplorer
    from jaxmc.session import batch_profile
    sigs = []
    for n in (None, 1):
        sess = _session(TRANSFER, str(cfg), devices=n).compile()
        assert type(sess.engine) is TpuExplorer
        assert not isinstance(sess.engine, MeshExplorer)
        sigs.append(sess.layout_sig)
    assert sigs[0] == sigs[1]
    assert batch_profile(SessionConfig(host_seen=True, devices=4,
                                       **base)) is None


def test_cli_flag_reaches_the_session():
    import argparse
    ns = argparse.Namespace(spec="x.tla", devices=4, include=[],
                            sample=[800, 40, 60])
    assert SessionConfig.from_args(ns).n_devices == 4


# ------------------------------------------------ spans, scopes, counters

MESH_SCOPES = ("jaxmc.mesh.route", "jaxmc.mesh.exchange",
               "jaxmc.mesh.scalars", "jaxmc.expand", "jaxmc.keys",
               "jaxmc.merge.sort", "jaxmc.merge.probe",
               "jaxmc.merge.scatter", "jaxmc.compact", "jaxmc.scan")


@pytest.mark.parametrize("exchange", ["a2a", "gather"])
def test_lowered_superstep_names_its_kernels(exchange, monkeypatch):
    import jax.numpy as jnp
    monkeypatch.setenv("JAXMC_MESH_EXCHANGE", exchange)
    sess = _session(os.path.join(SPECS, "constoy.tla"),
                    os.path.join(SPECS, "constoy.cfg"), devices=4).compile()
    ex = sess.engine
    assert ex.exchange == exchange
    SC, FC, TRL, VC = 256, 64, 16, 64
    fn = ex._get_mesh_resident_step(SC, FC, TRL, VC)
    i32 = jnp.int32
    args = (jnp.zeros((4, SC, ex.K), i32), jnp.zeros((4,), i32),
            jnp.zeros((4, FC, ex.PW), i32), jnp.zeros((4,), i32),
            jnp.zeros((4, TRL, FC, ex.PW), i32),
            jnp.zeros((4, TRL, FC), i32)) + (i32(0),) * 4
    low = fn.__wrapped__.lower(*args)
    text = low.as_text(debug_info=True)
    for scope in MESH_SCOPES:
        assert f"/{scope}/" in text, scope
    # the exchange's collective is in the program
    coll = "all_to_all" if exchange == "a2a" else "all_gather"
    assert f"stablehlo.{coll}" in text
    assert "jaxmc." not in low.as_text()


def test_spans_and_counters_of_one_search(tmp_path):
    cfg = tmp_path / "t.cfg"
    cfg.write_text(_cfg_text(2, 3, 5))
    tel = obs.Telemetry()
    with obs.use(tel):
        sess = _session(TRANSFER, str(cfg), tel, devices=4)
        sess.explore()
        before = dict(tel.counters)
        phases0 = {p["name"]: p["count"] for p in tel.phase_list()}
        sess.explore()
    phases = {p["name"]: p["count"] - phases0.get(p["name"], 0)
              for p in tel.phase_list()}
    # a warm search: one of each, all inside session.py's `search`
    for name in ("search", "search.init", "search.seed", "search.dispatch",
                 "search.fetch", "search.finish"):
        assert phases[name] == 1, (name, phases)
    events = [e for e in tel.recent_events() if e["ev"] == "span"]
    disp = [e for e in events if e["name"] == "search.dispatch"][-1]
    assert disp["attrs"]["fresh_compile"] is False
    assert disp["attrs"]["maxlvl"] >= 7
    rise = {k: tel.counters[k] - before.get(k, 0) for k in tel.counters}
    assert rise["search.rows_valid"] == 256 - 9
    assert rise["search.rows_new"] == 166 - 9
    assert rise["mesh.host_syncs"] == 1
    assert rise["mesh.exchange_bytes"] > 0
    assert rise["search.seen_slots"] % (7 * 4) == 0
    g = tel.gauges
    assert g["mesh.devices"] == 4 and g["mesh.exchange"] == "a2a"
    assert 1.0 <= g["mesh.shard_balance"] < 2.0


# ------------------------------------------------ the CLI

def _cli(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAXMC_COMPILE_CACHE="off",
               JAXMC_LEDGER="off", PYTHONPATH=REPO)
    # the child asks XLA for its own host devices: nothing by hand here
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-m", "jaxmc", "check", *argv],
                          cwd=REPO, env=env, capture_output=True, text=True,
                          timeout=600)


def test_cli_devices_4_on_cpu_needs_no_environment(tmp_path):
    cfg = tmp_path / "t.cfg"
    cfg.write_text(_cfg_text(2, 3, random.Random(7).randrange(2 ** 31)))
    p = _cli(TRANSFER, "--cfg", str(cfg), "--backend", "cpu", "--devices",
             "4", "--quiet")
    assert p.returncode == 0, p.stderr[-2000:]
    assert "256 states generated, 166 distinct states found" in p.stdout
    assert "No error has been found" in p.stdout


def test_cli_exits_2_naming_both_counts_with_no_verdict(tmp_path):
    """One visible device, four asked: on a real platform nothing grants
    more (the CPU's count is pinned to 1 here through jax's own flag)."""
    cfg = tmp_path / "t.cfg"
    cfg.write_text(_cfg_text(2, 3, 0))
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAXMC_COMPILE_CACHE="off",
               JAXMC_LEDGER="off", PYTHONPATH=REPO, JAX_NUM_CPU_DEVICES="1")
    env.pop("XLA_FLAGS", None)
    code = ("import sys, jax\n"
            "jax.devices()\n"       # the backend is up with ONE device
            "from jaxmc.cli import main\n"
            "sys.exit(main(sys.argv[1:]))\n")
    p = subprocess.run(
        [sys.executable, "-c", code, "check", TRANSFER, "--cfg", str(cfg),
         "--backend", "cpu", "--devices", "4", "--quiet"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 2, (p.stdout, p.stderr[-2000:])
    assert "--devices 4 asked, 1 visible" in p.stderr
    assert "states generated" not in p.stdout
    assert "No error" not in p.stdout
