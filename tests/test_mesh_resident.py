r"""Mesh-resident sharded BFS (ISSUE 8 + ISSUE 10): owner-routed a2a
dedup, O(new) rank-merge, multi-level fused supersteps.

Pins, on repo-local models only (no reference corpus needed):
  * a2a is the DEFAULT exchange for D > 1 (JAXMC_MESH_EXCHANGE
    overrides);
  * the resident loop reads ONE scalar ring per SUPERSTEP —
    mesh.host_syncs counts supersteps (<= level records, < on any
    multi-level run), no row traffic; JAXMC_MESH_SUPERSTEP=1 restores
    one-sync-per-level exactly;
  * the shard-local rank merge against references that share no
    code with it: counts equal the INTERPRETER's, seen-shard occupancy
    equals the host_seen engine's native store size, a violation
    trace has the interpreter's kind, depth and labels and replays
    through its Next — at D in {1, 2, 4} and under the mesh_skew
    fault; superstep vs one-level is BIT-IDENTICAL (counts, violation
    traces), mid-superstep capacity growth included;
  * a second run on a warm engine has window_recompiles == 0, and a
    FRESH engine starting from the persisted (module, layout, D,
    exchange) capacity profile compiles exactly once with zero
    growth redos;
  * checkpoint/resume parity under a2a at D=4 — truncation resume,
    a SIGTERM drain at a superstep boundary, and a SIGKILL mid-run
    (chaos) all finish with totals and traces bit-identical to the
    uninterrupted run;
  * the mesh_skew fault forces every state onto shard 0: the spill
    pass drains the overflow and counts/traces stay exact.
"""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from jaxmc.front.cfg import ModelConfig, parse_cfg
from jaxmc.sem.modules import Loader, bind_model

SPECS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "specs")
REPO = os.path.dirname(SPECS)


def load(name, cfg_name=None, no_deadlock=False):
    p = os.path.join(SPECS, name + ".tla")
    m = Loader([SPECS]).load_path(p)
    if cfg_name is None and os.path.exists(
            os.path.join(SPECS, name + ".cfg")):
        cfg_name = name
    if cfg_name:
        cfg = parse_cfg(open(os.path.join(SPECS,
                                          cfg_name + ".cfg")).read())
    else:
        cfg = ModelConfig(specification="Spec")
    if no_deadlock:
        cfg.check_deadlock = False
    return bind_model(m, cfg)


@pytest.fixture(autouse=True)
def _no_profile_store(tmp_path, monkeypatch):
    # isolate every test's capacity profiles (and keep the box-wide
    # store out of the parity measurements)
    monkeypatch.setenv("JAXMC_PROFILE_STORE", str(tmp_path / "prof"))


def meshd(D):
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:D]), ("d",))


def mesh4():
    return meshd(4)


class TestExchangeDefault:
    def test_a2a_default_for_multidevice(self):
        from jaxmc.backend.mesh import MeshExplorer
        me = MeshExplorer(load("constoy"))
        assert me.D > 1 and me.exchange == "a2a"
        assert me._exchange_src == "default"

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("JAXMC_MESH_EXCHANGE", "gather")
        from jaxmc.backend.mesh import MeshExplorer
        me = MeshExplorer(load("constoy"))
        assert me.exchange == "gather"
        assert me._exchange_src == "JAXMC_MESH_EXCHANGE"

    def test_explicit_arg_beats_env(self, monkeypatch):
        monkeypatch.setenv("JAXMC_MESH_EXCHANGE", "gather")
        from jaxmc.backend.mesh import MeshExplorer
        me = MeshExplorer(load("constoy"), exchange="a2a")
        assert me.exchange == "a2a"

    def test_single_device_defaults_gather(self):
        import jax
        from jax.sharding import Mesh
        from jaxmc.backend.mesh import MeshExplorer
        me = MeshExplorer(load("constoy"),
                          mesh=Mesh(np.array(jax.devices()[:1]),
                                    ("d",)))
        assert me.exchange == "gather"


class TestResidentLoop:
    def test_host_syncs_counts_supersteps_scalars_only(self):
        from jaxmc import obs
        from jaxmc.backend.mesh import MeshExplorer
        from jaxmc.engine.explore import Explorer
        ri = Explorer(load("constoy")).run()
        tel = obs.Telemetry()
        with obs.use(tel):
            me = MeshExplorer(load("constoy"), exchange="a2a")
            r = me.run()
        assert (r.generated, r.distinct, r.ok) == \
            (ri.generated, ri.distinct, ri.ok)
        # one scalar-ring read per SUPERSTEP (ISSUE 10): the adaptive
        # controller fuses levels, so syncs < level records on this
        # multi-level model; a clean run still pulls NO rows
        levels = len(tel.levels)
        assert tel.counters["mesh.host_syncs"] == \
            tel.gauges["mesh.supersteps"] <= levels
        assert tel.counters["mesh.host_syncs"] < levels
        assert tel.gauges["mesh.superstep_levels"] >= 2
        assert "mesh.row_syncs" not in tel.counters
        assert tel.counters["mesh.exchange_bytes"] > 0
        assert tel.gauges["mesh.exchange"] == "a2a"
        assert tel.gauges["dedup.mode"].startswith("fp128")
        assert tel.gauges["mesh.shard_balance"] >= 1.0

    def test_superstep_one_pins_one_sync_per_level(self, monkeypatch):
        monkeypatch.setenv("JAXMC_MESH_SUPERSTEP", "1")
        from jaxmc import obs
        from jaxmc.backend.mesh import MeshExplorer
        tel = obs.Telemetry()
        with obs.use(tel):
            r = MeshExplorer(load("constoy"), exchange="a2a").run()
        assert r.ok
        assert tel.counters["mesh.host_syncs"] == len(tel.levels)

    def test_second_run_zero_window_recompiles(self):
        from jaxmc import obs
        from jaxmc.backend.mesh import MeshExplorer
        tel = obs.Telemetry()
        with obs.use(tel):
            me = MeshExplorer(load("constoy"), exchange="a2a")
            r1 = me.run()
            lvl0 = len(tel.levels)
            r2 = me.run()
        fresh = sum(1 for lv in tel.levels[lvl0:]
                    if lv.get("fresh_compile"))
        assert fresh == 0
        assert (r2.generated, r2.distinct) == (r1.generated, r1.distinct)

    def test_profile_warms_a_fresh_engine(self):
        # run 1 persists the (module, layout_sig, D, exchange) profile;
        # a FRESH engine loads it, compiles exactly once, never grows
        from jaxmc import obs
        from jaxmc.backend.mesh import MeshExplorer
        MeshExplorer(load("viewtoy"), exchange="a2a").run()
        tel = obs.Telemetry()
        with obs.use(tel):
            me = MeshExplorer(load("viewtoy"), exchange="a2a")
            assert me._mesh_caps_hint, "profile did not load"
            me.run()
        assert sum(1 for lv in tel.levels
                   if lv.get("fresh_compile")) == 1
        assert not any(lv.get("redo") for lv in tel.levels)

    def test_profile_is_keyed_by_device_count(self):
        from jaxmc.compile.cache import profile_path
        p4 = profile_path("m", "sig", variant="mesh-d4-a2a")
        p8 = profile_path("m", "sig", variant="mesh-d8-a2a")
        assert p4 != p8

    def test_gather_and_a2a_bit_identical(self):
        from jaxmc.backend.mesh import MeshExplorer
        rg = MeshExplorer(load("constoy"), exchange="gather").run()
        ra = MeshExplorer(load("constoy"), exchange="a2a").run()
        assert (rg.generated, rg.distinct, rg.ok) == \
            (ra.generated, ra.distinct, ra.ok)

    def test_d4_counts_and_view_symmetry_parity(self):
        from jaxmc.engine.explore import Explorer
        from jaxmc.backend.mesh import MeshExplorer
        for name, kw in (("viewtoy", {}),
                         ("symtoy", dict(no_deadlock=True))):
            ri = Explorer(load(name, **kw)).run()
            r = MeshExplorer(load(name, **kw), mesh=mesh4(),
                             exchange="a2a").run()
            assert (r.generated, r.distinct, r.ok) == \
                (ri.generated, ri.distinct, ri.ok), name

    def test_violation_trace_parity_with_hostloop(self):
        # the resident loop and the legacy host loop must report the
        # SAME counterexample (rows ride the device ring vs per-level
        # host pulls — one provenance contract)
        from jaxmc.backend.mesh import MeshExplorer
        r_res = MeshExplorer(load("pcal_intro_buggy"),
                             exchange="a2a").run()
        os.environ["JAXMC_MESH_RESIDENT"] = "0"
        try:
            r_host = MeshExplorer(load("pcal_intro_buggy"),
                                  exchange="a2a").run()
        finally:
            os.environ.pop("JAXMC_MESH_RESIDENT", None)
        assert not r_res.ok and not r_host.ok
        assert r_res.violation.kind == r_host.violation.kind == "assert"
        assert [s for s, _ in r_res.violation.trace] == \
            [s for s, _ in r_host.violation.trace]
        assert [a for _, a in r_res.violation.trace] == \
            [a for _, a in r_host.violation.trace]


def _store_occupancy(model):
    """Distinct dedup keys the host_seen engine's native store ends
    with (`len(store)`): a count that shares nothing with
    bfs._rank_merge or the mesh's seen shards."""
    from jaxmc.backend.bfs import TpuExplorer
    ex = TpuExplorer(model, host_seen=True)
    r = ex.run()
    return r, ex._fp_occupancy


class TestMergeStrategies:
    """The shard-local rank merge (bfs._rank_merge under the mesh's
    valid-candidate compaction) against references that share no code
    with it."""

    @pytest.mark.parametrize("D", [1, 2, 4])
    def test_counts_and_occupancy_against_interp_and_host_store(self, D):
        """References: the interpreter for the counts; for the
        seen-shard occupancy the host_seen engine's native store,
        whose size is `len(store)`.  constoy discards states by
        CONSTRAINT: they stay fingerprinted (occupancy) and are never
        counted (distinct), so the two numbers differ and a stale or
        re-counted shard tail shows."""
        from jaxmc.engine.explore import Explorer
        from jaxmc.backend.mesh import MeshExplorer
        ri = Explorer(load("constoy")).run()
        rh, occ = _store_occupancy(load("constoy"))
        me = MeshExplorer(load("constoy"), mesh=meshd(D))
        r = me.run()
        assert me.D == D
        assert (r.generated, r.distinct, r.ok) == \
            (ri.generated, ri.distinct, ri.ok) == \
            (rh.generated, rh.distinct, rh.ok)
        assert me._fp_occupancy == occ > r.distinct

    def test_violation_trace_against_interpreter(self):
        """Reference: the interpreter.  On the default mesh (the 8
        virtual devices the fullsort pairing this replaces ran on) the
        resident mesh reports a counterexample of the same kind, the
        same depth and the same action labels.  Its states are another
        equally short behavior (frontier order differs across shards),
        so they are not compared: every step must be a transition the
        interpreter's Next gives that label."""
        from jaxmc.engine.explore import Explorer
        from jaxmc.sem.enumerate import (enumerate_init, enumerate_next,
                                         label_str)
        from jaxmc.backend.mesh import MeshExplorer
        model = load("pcal_intro_buggy")
        ri = Explorer(model).run()
        r = MeshExplorer(load("pcal_intro_buggy"), exchange="a2a").run()
        assert not r.ok and not ri.ok
        assert r.violation.kind == ri.violation.kind == "assert"
        trace = r.violation.trace
        assert len(trace) == len(ri.violation.trace)
        # ... the same labels, in the order of ITS behavior: which of the
        # equally short ones a shard meets first hangs on the order of the
        # keys (the labels were the interpreter's, one for one, under the
        # fingerprint up to ISSUE 51 and are a permutation under its
        # successor)
        assert sorted(a for _, a in trace) == \
            sorted(a for _, a in ri.violation.trace)
        ctx = model.ctx()
        assert trace[0][0] in enumerate_init(model.init, ctx,
                                             model.vars)
        for (st, _), (succ, lab) in zip(trace, trace[1:]):
            steps = []
            try:
                for s2, lbl in enumerate_next(model.next, ctx,
                                              model.vars, st):
                    steps.append((s2, label_str(lbl)))
            except Exception:
                pass  # the assert may fire during full expansion
            assert (succ, lab) in steps

    @pytest.mark.slow
    def test_view_symmetry_occupancy_d4(self):
        """References: the interpreter (counts) and the host_seen
        store (occupancy) on the VIEW and SYMMETRY rungs at D=4: the
        key basis (cfg VIEW lanes / orbit-canonical packing) must
        dedup in the shards as it does in the store."""
        from jaxmc.engine.explore import Explorer
        from jaxmc.backend.mesh import MeshExplorer
        for name, kw in (("viewtoy", {}),
                         ("symtoy", dict(no_deadlock=True))):
            ri = Explorer(load(name, **kw)).run()
            _, occ = _store_occupancy(load(name, **kw))
            me = MeshExplorer(load(name, **kw), mesh=mesh4(),
                              exchange="a2a")
            r = me.run()
            assert (r.generated, r.distinct, r.ok) == \
                (ri.generated, ri.distinct, ri.ok), name
            assert me._fp_occupancy == occ, name

    @pytest.mark.slow
    def test_under_skew_spill(self, monkeypatch):
        """References: the interpreter (counts) and the host_seen
        store (occupancy).  Hash skew (every state on shard 0) drives
        the spill pass and the most imbalanced merge inputs."""
        from jaxmc import faults
        from jaxmc.engine.explore import Explorer
        from jaxmc.backend.mesh import MeshExplorer
        ri = Explorer(load("constoy")).run()
        _, occ = _store_occupancy(load("constoy"))
        monkeypatch.setenv("JAXMC_FAULTS", "mesh_skew:n=1")
        faults.reset_for_tests()
        me = MeshExplorer(load("constoy"), exchange="a2a")
        assert me._skew
        r = me.run()
        assert (r.generated, r.distinct, r.ok) == \
            (ri.generated, ri.distinct, ri.ok)
        assert me._fp_occupancy == occ
        faults.reset_for_tests()


class TestSuperstep:
    """ISSUE 10: multi-level fused supersteps."""

    def test_superstep_vs_one_level_violation_parity(self,
                                                     monkeypatch):
        from jaxmc.backend.mesh import MeshExplorer
        monkeypatch.setenv("JAXMC_MESH_SUPERSTEP", "8")
        rs = MeshExplorer(load("pcal_intro_buggy"),
                          exchange="a2a").run()
        monkeypatch.setenv("JAXMC_MESH_SUPERSTEP", "1")
        r1 = MeshExplorer(load("pcal_intro_buggy"),
                          exchange="a2a").run()
        assert not rs.ok and not r1.ok
        assert (rs.generated, rs.distinct, rs.violation.kind) == \
            (r1.generated, r1.distinct, r1.violation.kind)
        assert [s for s, _ in rs.violation.trace] == \
            [s for s, _ in r1.violation.trace]
        assert [a for _, a in rs.violation.trace] == \
            [a for _, a in r1.violation.trace]

    def test_seen_overflow_mid_superstep_grows_and_redoes(
            self, monkeypatch):
        # pcal_intro_buggy outgrows the 256-key SC floor within the
        # first few levels; with an 8-level budget the overflow lands
        # MID-superstep — the offending level must roll back, grow,
        # and redo with counts/trace identical to a generously-capped
        # run
        from jaxmc import obs
        from jaxmc.backend.mesh import MeshExplorer
        monkeypatch.setenv("JAXMC_MESH_SUPERSTEP", "8")
        tel = obs.Telemetry()
        with obs.use(tel):
            r = MeshExplorer(load("pcal_intro_buggy"),
                             exchange="a2a").run()
        redos = [lv for lv in tel.levels if lv.get("redo")]
        assert redos, "no growth redo fired under the tiny SC floor"
        assert any("SC->" in lv["redo"] for lv in redos)
        rg = MeshExplorer(load("pcal_intro_buggy"), exchange="a2a",
                          mesh_caps={"SC": 1 << 14, "FC": 1 << 10,
                                     "TRL": 16, "GAM16": 32}).run()
        assert (r.generated, r.distinct, r.violation.kind) == \
            (rg.generated, rg.distinct, rg.violation.kind)
        assert [s for s, _ in r.violation.trace] == \
            [s for s, _ in rg.violation.trace]

    @pytest.mark.chaos
    def test_drain_at_superstep_boundary_resume_parity(
            self, tmp_path, monkeypatch):
        # request a drain (the SIGTERM path, jaxmc/drain.py) once the
        # search reaches depth 2: the loop must stop at the NEXT
        # superstep boundary, checkpoint, report drained=True — and a
        # resume must answer bit-identically to an uninterrupted run
        from jaxmc import drain, obs
        from jaxmc.backend.mesh import MeshExplorer
        monkeypatch.setenv("JAXMC_MESH_SUPERSTEP", "2")
        ck = str(tmp_path / "mesh_drain.ck")

        class DrainAt(obs.Telemetry):
            def level(self, lvl, **kw):
                super().level(lvl, **kw)
                if lvl >= 2 and not kw.get("redo"):
                    drain.request("test drain at superstep boundary")

        drain.clear()
        try:
            tel = DrainAt()
            with obs.use(tel):
                r1 = MeshExplorer(load("pcal_intro_buggy"),
                                  exchange="a2a", checkpoint_path=ck,
                                  checkpoint_every=0).run()
            assert r1.drained and r1.truncated and r1.ok
            assert os.path.exists(ck)
        finally:
            drain.clear()
        r2 = MeshExplorer(load("pcal_intro_buggy"), exchange="a2a",
                          resume_from=ck).run()
        rd = MeshExplorer(load("pcal_intro_buggy"),
                          exchange="a2a").run()
        assert (r2.ok, r2.generated, r2.distinct,
                r2.violation.kind) == \
            (rd.ok, rd.generated, rd.distinct, rd.violation.kind)
        assert [s for s, _ in r2.violation.trace] == \
            [s for s, _ in rd.violation.trace]


class TestCheckpointResume:
    def test_truncate_resume_parity_a2a_d4(self, tmp_path):
        from jaxmc.backend.mesh import MeshExplorer
        ck = str(tmp_path / "mesh.ck")
        r1 = MeshExplorer(load("pcal_intro_buggy"), mesh=mesh4(),
                          exchange="a2a", max_states=20,
                          checkpoint_path=ck,
                          checkpoint_every=0).run()
        assert r1.truncated and os.path.exists(ck)
        r2 = MeshExplorer(load("pcal_intro_buggy"), mesh=mesh4(),
                          exchange="a2a", resume_from=ck).run()
        rd = MeshExplorer(load("pcal_intro_buggy"), mesh=mesh4(),
                          exchange="a2a").run()
        assert (r2.ok, r2.violation.kind) == (rd.ok, rd.violation.kind)
        assert [s for s, _ in r2.violation.trace] == \
            [s for s, _ in rd.violation.trace]

    @pytest.mark.chaos
    @pytest.mark.slow
    def test_kill_resume_parity_a2a_d4(self, tmp_path):
        # SIGKILL the run mid-search (run_kill fault at the mesh
        # engine's level boundary), resume from its checkpoint, and
        # require bit-identical totals + trace vs an uninterrupted run
        from jaxmc import faults
        from jaxmc.backend.mesh import MeshExplorer
        ck = str(tmp_path / "mesh_kill.ck")
        code = f"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
import jax
jax.config.update("jax_platforms", "cpu")
import sys
sys.path.insert(0, {REPO!r})
from jaxmc.front.cfg import ModelConfig
from jaxmc.sem.modules import Loader, bind_model
from jaxmc.backend.mesh import MeshExplorer
m = bind_model(Loader([{SPECS!r}]).load_path(
    os.path.join({SPECS!r}, "pcal_intro_buggy.tla")),
    ModelConfig(specification="Spec"))
MeshExplorer(m, exchange="a2a", checkpoint_path={ck!r},
             checkpoint_every=0).run()
"""
        env = dict(os.environ, PYTHONPATH=REPO,
                   JAXMC_FAULTS="run_kill:level=3:engine=mesh",
                   JAXMC_PROFILE_STORE=str(tmp_path / "prof"))
        env.pop("JAXMC_FAULTS_STATE", None)
        p = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, env=env,
                           timeout=600)
        assert p.returncode == -9, (p.returncode, p.stderr[-500:])
        assert os.path.exists(ck), "no checkpoint before the kill"
        faults.reset_for_tests()
        r2 = MeshExplorer(load("pcal_intro_buggy"), mesh=mesh4(),
                          exchange="a2a", resume_from=ck).run()
        rd = MeshExplorer(load("pcal_intro_buggy"), mesh=mesh4(),
                          exchange="a2a").run()
        assert (r2.ok, r2.violation.kind, r2.generated, r2.distinct) \
            == (rd.ok, rd.violation.kind, rd.generated, rd.distinct)
        assert [s for s, _ in r2.violation.trace] == \
            [s for s, _ in rd.violation.trace]


class TestForcedSpill:
    def test_skew_routes_everything_to_shard_zero(self, monkeypatch):
        from jaxmc import faults
        monkeypatch.setenv("JAXMC_FAULTS", "mesh_skew")
        faults.reset_for_tests()
        from jaxmc.backend.mesh import MeshExplorer
        from jaxmc.engine.explore import Explorer
        ri = Explorer(load("constoy")).run()
        me = MeshExplorer(load("constoy"), exchange="a2a")
        assert me._skew
        keys = np.arange(40, dtype=np.int32).reshape(8, 5)
        assert (me._owner_from_keys(keys) == 0).all()
        r = me.run()
        assert (r.generated, r.distinct, r.ok) == \
            (ri.generated, ri.distinct, ri.ok)
        faults.reset_for_tests()

    @pytest.mark.parametrize("D", [4, 8])
    def test_forced_spill_parity(self, D, monkeypatch):
        # two passes: measure the peak per-destination bucket under
        # skew, then pin FC and size gamma so the peak level lands in
        # the SPILL window (B < need <= B+SB) — the spill pass must
        # drain it with counts and trace bit-identical to the
        # spill-free skewed run, and (ISSUE 31: the buckets are slices
        # of the destination-sorted payload) with the counts of the
        # ONE-CHIP engine, which routes nothing
        from jaxmc import faults, obs
        from jaxmc.backend.bfs import TpuExplorer
        from jaxmc.backend.mesh import MeshExplorer
        r0 = TpuExplorer(load("pcal_intro_buggy")).run()
        monkeypatch.setenv("JAXMC_FAULTS", "mesh_skew:n=3")
        faults.reset_for_tests()
        tel = obs.Telemetry()
        with obs.use(tel):
            m1 = MeshExplorer(load("pcal_intro_buggy"), mesh=meshd(D),
                              exchange="a2a")
            assert m1._skew
            r1 = m1.run()
        assert m1._spill_rows == 0  # generous gamma: no spill yet
        lv = [(r["max_bucket"], r["fc"]) for r in tel.levels
              if r.get("max_bucket")]
        fcmax = max(fc for _, fc in lv)
        mb = max(v for v, _ in lv)
        A = m1.A
        m2 = MeshExplorer(load("pcal_intro_buggy"), mesh=meshd(D),
                          exchange="a2a",
                          mesh_caps={"SC": 1 << 15, "FC": fcmax,
                                     "TRL": 16, "GAM16": 1})
        assert m2._skew
        m2._a2a_gamma = (mb - 1) * D / (A * fcmax)
        r2 = m2.run()
        assert m2._spill_rows > 0, "spill pass never drained a row"
        assert (r2.ok, r2.violation.kind) == (r1.ok, r1.violation.kind)
        assert [s for s, _ in r2.violation.trace] == \
            [s for s, _ in r1.violation.trace]
        assert (r2.generated, r2.distinct) == (r1.generated, r1.distinct) \
            == (r0.generated, r0.distinct)
        assert (r2.ok, r2.violation.kind) == (r0.ok, r0.violation.kind)
        faults.reset_for_tests()


class TestEdgeStream:
    def test_gather_edge_stream_covers_foreign_owned_rows(self):
        # regression (review r8): the legacy gather step's host-side
        # edge stream is read from DEVICE 0 ONLY — its explore mask
        # must cover every valid exchanged candidate, not just the
        # rows device 0 happens to own (recomputing validity from the
        # ownership-masked keys dropped ~(D-1)/D of the edges, which
        # would silently skip refinement/liveness checks on them)
        import time as _t
        import jax.numpy as jnp
        from jaxmc.backend.mesh import MeshExplorer
        me = MeshExplorer(load("viewtoy_scaled"), exchange="gather")
        me.collect_edges = True   # forces the edge-stream outputs
        init_rows, explored, n_init, err = me._prepare_init(
            _t.time(), [])
        assert err is None
        D, SC, FC = me.D, 256, 64
        seen, frontier, fcount, scount = me._init_shards(
            init_rows, explored, D, SC, FC)
        step = me._get_mesh_step(SC, FC)
        outs = step(jnp.asarray(seen), jnp.asarray(scount),
                    jnp.asarray(frontier), jnp.asarray(fcount))
        tot_gen = int(np.asarray(outs[5])[0])
        assert tot_gen > me.D  # wide enough to spread over shards
        eexp0 = np.asarray(outs[19][0])
        assert int(eexp0.sum()) == tot_gen


class TestWarmSessionLegD2:
    def test_warm_leg_constoy_d2(self, tmp_path):
        # the warm-window leg the deleted mesh harness ran in a child
        # process (ISSUE 43), on the normal path: `SessionConfig(devices=2)`
        # twice on conftest's virtual devices; the second search is the
        # window
        from jaxmc import obs
        from jaxmc.session import CheckSession, SessionConfig
        tel = obs.Telemetry()
        with obs.use(tel):
            sess = CheckSession(SessionConfig(
                spec=os.path.join(SPECS, "constoy.tla"), backend="jax",
                platform="cpu", devices=2), tel=tel)
            sess.explore()
            site = tel.prof.sites["mesh.superstep"]
            lvl0, sync0, disp0 = (len(tel.levels),
                                  tel.counters["mesh.host_syncs"],
                                  site.dispatches)
            r = sess.explore()
        levels = len(tel.levels) - lvl0
        host_syncs = tel.counters["mesh.host_syncs"] - sync0
        supersteps = site.dispatches - disp0
        assert r.ok and sess.engine.D == 2
        assert tel.gauges["mesh.devices"] == 2
        assert (r.generated, r.distinct) == (43, 21)
        # warm window: no program compiled inside it
        assert not any(lv.get("fresh_compile")
                       for lv in tel.levels[lvl0:])
        # scalar-ring reads only: one per superstep, never more than
        # the level count — and the warm window (learned MSL) must
        # actually fuse levels
        assert supersteps == host_syncs <= levels
        assert host_syncs < levels
        assert sess.engine.exchange == "a2a"
        assert tel.gauges["mesh.exchange"] == "a2a"
        out = str(tmp_path / "leg.json")
        tel.write_metrics(out, result={
            "ok": bool(r.ok), "distinct": int(r.distinct),
            "generated": int(r.generated)})
        art = json.load(open(out))
        assert art["schema"] == "jaxmc.metrics/4"
        assert art["gauges"]["mesh.devices"] == 2
        assert art["counters"]["mesh.host_syncs"] == sync0 + supersteps
