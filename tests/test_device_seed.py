r"""The seed is made where it is used (ISSUE 35): every engine's search
tables — seen, frontier, the mesh's trace ring — are filled on the device
from their heads (`TpuExplorer._device_table`), and no search start builds
a capacity-sized table on the host.

Held here, at toy size on XLA:CPU:
  * the tables each engine hands its FIRST dispatch, pulled back to the
    host, are row for row the arrays the host code up to PR 34 built with
    `np.full` (written out again below, and for the mesh through
    `_init_shards` at full capacity, the one layout rule) — resident capped
    and uncapped, the level engine, the mesh at D = 2 and 4 with and
    without the trace ring (empty seen slots: validity lane 1; ring fill
    SENTINEL / -1; every table sharded over its leading axis, one shard a
    device), and a `--resume` from a truncation checkpoint in resident and
    mesh form;
  * three `explore()` calls on one session give the plain reference's
    counts on every engine, and the second and third compile nothing:
    the fills are programs of the warm-up search.
"""

import importlib.util
import os

import numpy as np
import pytest

from jaxmc import obs
from jaxmc.front.cfg import parse_cfg
from jaxmc.sem.modules import Loader, bind_model

pytest.importorskip("jax")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRANSFER = os.path.join(REPO, "bench", "specs", "transfer_scaled.tla")
#: 3 procs / MaxMoney 3: 4,963 generated / 2,455 distinct, 27 init states
TOY = (3, 3)
RES_CAPS = {"SC": 4096, "FCap": 1024, "AccCap": 4096, "VC": 256}
MESH_CAPS = {"SC": 2048, "FC": 512, "TRL": 16, "GAM16": 32, "MSL": 16,
             "VC": 512}


@pytest.fixture(autouse=True)
def _no_capacity_profiles(monkeypatch, tmp_path):
    monkeypatch.setenv("JAXMC_CAP_PROFILE", "0")
    monkeypatch.setenv("JAXMC_PROFILE_STORE", str(tmp_path / "prof"))


def _cfg_text(procs, max_money):
    return ("SPECIFICATION Spec\nINVARIANT AliceBounded\nCONSTANTS\n"
            "  Procs = {%s}\n  MaxMoney = %d\n"
            % (", ".join("p%d" % (i + 1) for i in range(procs)), max_money))


def _model(size=TOY):
    mod = Loader([os.path.dirname(TRANSFER)]).load_path(TRANSFER)
    return bind_model(mod, parse_cfg(_cfg_text(*size)))


def _reference(size=TOY):
    spec = importlib.util.spec_from_file_location(
        "plain_reference", os.path.join(REPO, "bench", "reference",
                                        "transfer_scaled.py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    want = reference.explore(*size)
    return (want["generated"], want["distinct"], want["diameter"], True,
            False)


def _answer(res):
    return (res.generated, res.distinct, res.diameter, res.ok,
            bool(res.truncated))


def _meshd(D):
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:D]), ("d",))


class _FirstDispatch(Exception):
    """Raised in place of an engine's first dispatch, carrying its
    operands as the engine handed them over."""


def _first_dispatch(ex, getter):
    """Run `ex` up to its first dispatch of the program `getter` returns
    and give back that dispatch's operands (device arrays, untouched: the
    program never ran, so nothing was donated)."""
    real = getattr(ex, getter)

    def spy(*key):
        real(*key)  # the engine's own cache bookkeeping, no dispatch

        def stop(*operands):
            raise _FirstDispatch(operands)
        return stop
    setattr(ex, getter, spy)
    try:
        with pytest.raises(_FirstDispatch) as stopped:
            ex.run()
    finally:
        delattr(ex, getter)
    return stopped.value.args[0]


def _host_table(shape, head, fill_row=None):
    """The host table as every search start built it up to PR 34:
    `np.full` at full capacity, the head in its first rows."""
    from jaxmc.backend.bfs import SENTINEL
    table = np.full(shape, SENTINEL, np.int32)
    if fill_row is not None:
        table[:] = fill_row
    table[tuple(slice(0, n) for n in np.shape(head))] = head
    return table


def _init_heads(ex):
    """(seen head, frontier head) of a fresh one-chip search: the init
    keys in key order, the explored init rows packed."""
    import time
    init_rows, explored, n_init, err = ex._prepare_init(time.time(), [])
    assert err is None and n_init > 1
    keys, packed, povf = ex._host_keys(init_rows)
    assert not povf
    order = np.lexsort(tuple(keys[:, i] for i in reversed(range(ex.K))))
    return keys[order], packed[explored]


# --------------------------------------------------------- the fill itself

@pytest.mark.parametrize("fill", ["word", "row"])
def test_device_table_is_the_host_table(fill):
    """`_device_table` against `np.full` + head, any rank: a word or a row
    of words as the fill, a head in the leading corner, no head, an empty
    head, a head that IS the table; a head that does not fit is refused."""
    from jaxmc.backend.bfs import SENTINEL, TpuExplorer
    row = (1, int(SENTINEL), int(SENTINEL)) if fill == "row" else None
    kw = {"fill": np.asarray(row, np.int32)} if row else {}
    head = np.arange(12, dtype=np.int32).reshape(4, 3)
    for shape, h in (((16, 3), head), ((4, 3), head), ((16, 3), None),
                     ((16, 3), head[:0]),
                     ((2, 5, 4, 3), np.arange(24, dtype=np.int32)
                      .reshape(2, 1, 4, 3))):
        got = TpuExplorer._device_table(shape, h, **kw)
        want = _host_table(shape, h if h is not None else head[:0], row)
        assert got.dtype == np.int32 and np.array_equal(got, want), shape
    with pytest.raises(ValueError, match="does not fit"):
        TpuExplorer._device_table((2, 3), head)
    ring = TpuExplorer._device_table((2, 4, 8), fill=-1)
    assert np.array_equal(ring, np.full((2, 4, 8), -1, np.int32))


@pytest.mark.parametrize("D", [None, 4], ids=["one-chip", "mesh"])
def test_the_fill_holds_one_table_and_moves_nothing_between_devices(D):
    """Fill and head are ONE program writing ONE table-sized buffer: the
    executable's temporaries are nothing (an eager `.at[].set` on a
    filled table is a second table), its output on each device is its own
    shard, and on a mesh it holds no collective."""
    import re
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jaxmc.backend.bfs import SENTINEL, _table_program
    sharding = NamedSharding(_meshd(D), P("d")) if D else None
    lead = (D,) if D else ()
    head = jax.ShapeDtypeStruct(lead + (27, 5), jnp.int32,
                                sharding=sharding)
    compiled = _table_program(sharding).lower(
        head, shape=lead + (1 << 16, 5),
        fill=(1,) + (int(SENTINEL),) * 4).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 4 * 5 << 16   # a device's shard
    assert mem.temp_size_in_bytes == 0
    assert mem.argument_size_in_bytes == 4 * 27 * 5
    assert not re.search(r"all-to-all|all-gather|all-reduce|"
                         r"collective-permute", compiled.as_text())


# ------------------------------------------------ one chip: first dispatch

@pytest.mark.parametrize("capped", [False, True], ids=["uncapped", "capped"])
def test_resident_starts_from_the_host_built_tables(capped):
    """Capped or not, ONE path: the resident program's first operands are
    the parent's host tables at full capacity, and what the host handed
    over is the heads."""
    from jaxmc.backend.bfs import TpuExplorer
    tel = obs.Telemetry()
    with obs.use(tel):
        ex = TpuExplorer(_model(), resident=True, store_trace=False,
                         chunk=64, res_caps=dict(RES_CAPS),
                         **({"seen_cap": RES_CAPS["SC"]} if capped else {}))
        seen_head, fr_head = _init_heads(ex)
        seen, seen_count, frontier, fcount = _first_dispatch(
            ex, "_get_resident_run")[:4]
    assert (int(seen_count), int(fcount)) == (27, 27)
    assert np.array_equal(seen, _host_table(
        (RES_CAPS["SC"], ex.K), seen_head))
    assert np.array_equal(frontier, _host_table(
        (RES_CAPS["FCap"], ex.PW), fr_head))
    assert tel.counters["search.seed_bytes"] == 4 * 27 * (ex.K + ex.PW)
    assert tel.counters["seed.tables_s"] < 0.05


def test_level_engine_starts_from_the_host_built_tables():
    from jaxmc.backend.bfs import TpuExplorer
    tel = obs.Telemetry()
    with obs.use(tel):
        ex = TpuExplorer(_model())
        seen_head, fr_head = _init_heads(ex)
        seen, seen_count, frontier, fcount = _first_dispatch(
            ex, "_get_step")
    assert (int(seen_count), int(fcount)) == (27, 27)
    # the level engine's init-sized tables are its floors, FC = SC = 256;
    # before its first step the seen table grew (SENTINEL rows, on the
    # device) to seat 27 keys + A x FC candidates
    assert seen.shape[0] >= 27 + ex.A * 256 > 256
    assert np.array_equal(seen, _host_table(seen.shape, seen_head))
    assert np.array_equal(frontier, _host_table((256, ex.PW), fr_head))
    assert tel.counters["search.seed_bytes"] == 4 * 27 * (ex.K + ex.PW)


def test_resident_resume_starts_from_the_checkpoints_rows(tmp_path):
    """A `--resume`'s heads are the checkpoint's rows: the tables are the
    parent's (`np.full`, the rows in front), the upload is the rows and
    not the capacity, and the resumed search ends on the full counts."""
    from jaxmc.backend.bfs import TpuExplorer
    from jaxmc.engine.ckpt import load_checkpoint
    path = str(tmp_path / "toy.ck")
    kw = dict(resident=True, store_trace=False, chunk=64,
              res_caps=dict(RES_CAPS))
    cut = TpuExplorer(_model(), max_states=600, checkpoint_path=path,
                      **kw).run()
    assert cut.truncated and os.path.exists(path)
    _, ck = load_checkpoint(path, kind="device")
    assert 27 < len(ck["seen"]) < RES_CAPS["SC"] and len(ck["frontier"])
    tel = obs.Telemetry()
    with obs.use(tel):
        ex = TpuExplorer(_model(), resume_from=path, **kw)
        seen, seen_count, frontier, fcount = _first_dispatch(
            ex, "_get_resident_run")[:4]
    assert (int(seen_count), int(fcount)) == (len(ck["seen"]),
                                              len(ck["frontier"]))
    assert np.array_equal(seen, _host_table(
        (RES_CAPS["SC"], ex.K), ck["seen"]))
    assert np.array_equal(frontier, _host_table(
        (RES_CAPS["FCap"], ex.PW), ck["frontier"]))
    assert tel.counters["search.seed_bytes"] == \
        ck["seen"].nbytes + ck["frontier"].nbytes
    assert _answer(TpuExplorer(_model(), resume_from=path, **kw).run()) \
        == _reference()


# ------------------------------------------------------------- the mesh

def _sharded_one_a_device(arr, D):
    """`arr` is split over its leading axis alone, one shard a device."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    assert isinstance(arr.sharding, NamedSharding)
    assert arr.sharding.spec == P("d"), arr.sharding
    shards = arr.addressable_shards
    assert len(shards) == D == len({s.device for s in shards})
    assert all(s.data.shape == (1,) + arr.shape[1:] for s in shards)


@pytest.mark.parametrize("no_trace", [False, True], ids=["ring", "no_trace"])
@pytest.mark.parametrize("D", [2, 4])
def test_mesh_starts_from_the_host_built_shards(D, no_trace):
    """The superstep's first operands against `_init_shards` at FULL
    capacity (the host loop's and the multi-host loop's call: the one
    layout rule) and the parent's `np.full` rings; every table sharded as
    `_put` shards; level 0 kept on the host as the head rows."""
    import time
    from jaxmc.backend.bfs import SENTINEL
    from jaxmc.backend.mesh import MeshExplorer
    SC, FC, TRL = (MESH_CAPS[k] for k in ("SC", "FC", "TRL"))
    tel = obs.Telemetry()
    with obs.use(tel):
        ex = MeshExplorer(_model(), mesh=_meshd(D), exchange="a2a",
                          store_trace=not no_trace,
                          mesh_caps=dict(MESH_CAPS))
        init_rows, explored, _, err = ex._prepare_init(time.time(), [])
        assert err is None
        want_seen, want_fr, want_fc, want_sc = ex._init_shards(
            init_rows, explored, D, SC, FC)
        operands = _first_dispatch(ex, "_get_mesh_resident_step")
    seen, seen_count, frontier, fcount = operands[:4]
    K, PW = ex.K, ex.PW
    assert seen.shape == (D, SC, K) and frontier.shape == (D, FC, PW)
    assert np.array_equal(seen, want_seen)
    assert np.array_equal(frontier, want_fr)
    assert np.array_equal(seen_count, want_sc) and want_sc.sum() == 27
    assert np.array_equal(fcount, want_fc) and want_fc.sum() == 27
    # the empty-slot rule, said once more by hand: validity lane 1, the
    # data lanes SENTINEL — not the one-chip table's all-SENTINEL row
    empty = np.asarray(seen)[0, int(want_sc[0]):]
    assert (empty[:, 0] == 1).all() and (empty[:, 1:] == SENTINEL).all()
    tables = [seen, frontier]
    heads = 4 * D * (int(want_sc.max()) * K + int(want_fc.max()) * PW)
    if no_trace:
        assert len(operands) == 4 + 4   # no ring among the operands
    else:
        tr_rows, tr_src = operands[4:6]
        assert np.array_equal(tr_rows, np.full((D, TRL, FC, PW), SENTINEL,
                                               np.int32))
        assert np.array_equal(tr_src, np.full((D, TRL, FC), -1, np.int32))
        tables += [tr_rows, tr_src]
        # level 0 stays on the host for trace reconstruction: the heads
        (rows0, src0, fc0), = ex._levels
        assert src0 is None and fc0 == FC
        assert np.array_equal(rows0, want_fr[:, :int(want_fc.max())])
    for t in tables + [seen_count, fcount]:
        _sharded_one_a_device(t, D)
    assert tel.counters["search.seed_bytes"] == heads
    assert 0 < heads < sum(t.nbytes for t in tables) // 16


@pytest.mark.parametrize("no_trace", [False, True], ids=["ring", "no_trace"])
def test_mesh_resume_starts_from_the_checkpoints_rows(no_trace, tmp_path):
    """A mesh `--resume` against the parent's host code (the shards
    `np.full` with lane 1 and the checkpoint's in front; the ring filled
    level by level from the checkpoint's levels), and on to the counts of
    the uninterrupted run."""
    from jaxmc.backend.bfs import SENTINEL
    from jaxmc.backend.mesh import MeshExplorer
    from jaxmc.engine.ckpt import load_checkpoint
    D = 2
    SC, FC, TRL = (MESH_CAPS[k] for k in ("SC", "FC", "TRL"))
    path = str(tmp_path / "mesh.ck")
    kw = dict(mesh=_meshd(D), exchange="a2a", store_trace=not no_trace,
              mesh_caps=dict(MESH_CAPS))
    cut = MeshExplorer(_model(), max_states=600, checkpoint_path=path,
                       checkpoint_every=0, **kw).run()
    assert cut.truncated and os.path.exists(path)
    _, ck = load_checkpoint(path, kind="device")
    assert ck["depth"] >= 2 and int(ck["seen_counts"].max()) > 27
    tel = obs.Telemetry()
    with obs.use(tel):
        ex = MeshExplorer(_model(), resume_from=path, **kw)
        operands = _first_dispatch(ex, "_get_mesh_resident_step")
    seen, seen_count, frontier, fcount = operands[:4]
    K, PW = ex.K, ex.PW
    want_seen = np.full((D, SC, K), SENTINEL, np.int32)
    want_seen[:, :, 0] = 1
    want_seen[:, :ck["SC"]] = ck["seen"]
    want_fr = np.full((D, FC, PW), SENTINEL, np.int32)
    want_fr[:, :ck["FC"]] = ck["frontier"]
    assert np.array_equal(seen, want_seen)
    assert np.array_equal(frontier, want_fr)
    assert np.array_equal(seen_count, ck["seen_counts"])
    assert np.array_equal(fcount, ck["fcount"])
    heads = 4 * D * (int(ck["seen_counts"].max()) * K
                     + int(ck["fcount"].max()) * PW)
    tables = [seen, frontier]
    if not no_trace:
        levels = ck["levels"][1:]
        assert len(levels) == ck["depth"]
        want_rows = np.full((D, TRL, FC, PW), SENTINEL, np.int32)
        want_src = np.full((D, TRL, FC), -1, np.int32)
        for lvl, (rows, src, _fc) in enumerate(levels):
            want_rows[:, lvl, :rows.shape[1]] = rows
            want_src[:, lvl, :src.shape[1]] = src
        tr_rows, tr_src = operands[4:6]
        assert np.array_equal(tr_rows, want_rows)
        assert np.array_equal(tr_src, want_src)
        tables += [tr_rows, tr_src]
        widest = max(rows.shape[1] for rows, _, _ in levels)
        heads += 4 * D * len(levels) * widest * (PW + 1)
    for t in tables:
        _sharded_one_a_device(t, D)
    assert tel.counters["search.seed_bytes"] == heads
    assert _answer(MeshExplorer(_model(), resume_from=path, **kw).run()) \
        == _reference()


# ------------------------------- three searches on one session, one compile

ENGINES = {
    "level": dict(),
    "resident": dict(resident=True, no_trace=True, res_caps=RES_CAPS,
                     chunk=64),
    "mesh": dict(devices=2, res_caps=MESH_CAPS),
    "mesh-no-trace": dict(devices=2, no_trace=True, res_caps=MESH_CAPS),
}


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_three_searches_keep_the_counts_and_the_window_compiles_no_fill(
        engine, tmp_path):
    """`explore()` three times on one session: the reference's counts
    each time, the same heads handed over each time, and after the first
    search `compile.xla_compiles` does not rise — the shapes of the fills
    are constants of a model, so they compile in the warm-up search."""
    from jaxmc.session import CheckSession, SessionConfig
    cfg = tmp_path / "toy.cfg"
    cfg.write_text(_cfg_text(*TOY))
    want = _reference()
    tel = obs.Telemetry()
    with obs.use(tel):
        sess = CheckSession(SessionConfig(
            spec=TRANSFER, cfg=str(cfg), backend="jax", platform="cpu",
            **ENGINES[engine]), tel=tel)
        seen = []
        for i in range(3):
            assert _answer(sess.explore()) == want, (engine, i)
            seen.append((tel.counters["compile.xla_compiles"],
                         tel.counters["search.seed_bytes"]))
    (c1, b1), (c2, b2), (c3, b3) = seen
    assert c1 > 0 and c1 == c2 == c3, seen
    assert 0 < b1 and (b2, b3) == (2 * b1, 3 * b1)
    # heads, not capacity: KBs where the tables are hundreds of KBs
    assert b1 < tel.gauges["search.table_bytes"] // 16
