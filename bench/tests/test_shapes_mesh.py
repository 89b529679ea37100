"""Bytes that leave a chip in a search of the sharded engine, on hand-worked
rows; the program's own counter against the same shapes."""

import os

import pytest

import lib
import shapes_mesh as M


def test_hand_worked_rows():
    # 3 instances x 8 frontier rows = 24 candidate slots a shard; gamma 2
    # over 4 devices: buckets of ceil(24 * 2 / 4) = 12 rows, spill 3
    assert M.buckets(3, 8, 32, 4) == (12, 3)
    # a row: validity lane + 4 key words + 2 state words + source = 8 words
    assert M.row_bytes(2, 4) == 32
    # to each of 3 peers, 15 rows of 32 B, a level
    assert M.level_bytes_leaving_a_chip(3, 8, 32, 4, 2, 4) == 3 * 15 * 32
    assert M.search_bytes_leaving_a_chip(7, 3, 8, 32, 4, 2, 4) == \
        7 * 3 * 15 * 32
    # the floor: a bucket never smaller than the frontier's share, so a
    # sparse level cannot hand on a frontier narrower than its shape
    assert M.buckets(1, 64, 16, 4) == (16, 4)
    # gamma 2.5 (GAM16 40), a count that does not divide
    assert M.buckets(13, 10, 40, 4) == (82, 20)
    # one device sends nothing
    assert M.level_bytes_leaving_a_chip(3, 8, 32, 1, 2, 4) == 0


def test_routed_rows_are_the_rows_that_exist():
    # 1600 candidates on 4 chips: 400 a chip, 300 of them owned elsewhere
    assert M.routed_bytes_leaving_a_chip(1600, 4, 2, 4) == 300 * 32
    # two chips: half leave; one chip: none
    assert M.routed_bytes_leaving_a_chip(1600, 2, 2, 4) == 400 * 32
    assert M.routed_bytes_leaving_a_chip(1600, 1, 2, 4) == 0
    # the cell: 4,767,576 generated -> 28.6 MB leave a chip a search,
    # against 1.33 GB of bucket slots
    res = lib.resolve("mesh-recheck-4p")
    run = {"pins": res["pins"], "mix": res["mix"], "cell": res["cell"]}
    assert M.routed_of_run(run) == 4767576 * 3 / 16 * 32 == 28605456.0
    one = lib.resolve("desk-recheck-4p8")
    assert M.routed_of_run({"pins": one["pins"], "mix": one["mix"],
                            "cell": one["cell"]}) is None


def test_the_share_of_the_peak():
    # 200 GB leaving a chip whose links carry 1600 Gbit/s = 200 GB/s take
    # a second at best
    assert M.ici_share(200e9, 1.0, 1600e9) == pytest.approx(100.0)
    assert M.ici_share(200e9, 4.0, 1600e9) == pytest.approx(25.0)


def test_the_cells_buckets_from_its_pins():
    """What the links carry, padding and all: 1.33 GB leave a chip a search
    at the pinned capacities, 46 times the rows that exist."""
    pins = lib.resolve("mesh-recheck-4p")["pins"]
    caps = pins["res_caps"]
    b, sb = M.buckets(pins["expand_instances"], caps["FC"], caps["GAM16"], 4)
    assert M.search_bytes_leaving_a_chip(
        len(pins["levels"]), pins["expand_instances"], caps["FC"],
        caps["GAM16"], 4, 2, 4) == len(pins["levels"]) * 3 * (b + sb) * 32 \
        == 1329070080


_DRIVE = """
import json, sys, time
sys.path.insert(0, {bench!r})
import lib
res = lib.resolve("mesh-recheck-4p")
caps = {caps!r}
res["mix"] = dict(res["mix"], session=dict(res["mix"]["session"],
                                           res_caps=caps))
driver = lib.load_module(res["driver_path"], "bench_driver_mesh_bytes")
out = driver.run(dict(res, seed=3, seconds=0.5, trace=False,
                      rehearsal=True, t0=time.time()))
print("OUT " + json.dumps(out))
"""


def test_the_programs_counter_counts_the_same_buckets():
    """`mesh.exchange_bytes` counts the whole mesh, own bucket included:
    D x D x (B + SB) rows a level; what leaves a chip is (D-1)/D^2 of it.
    At toy size on four virtual CPU devices, pinned capacities; a process
    of its own, because this one's XLA:CPU may be up with one device."""
    import json
    import subprocess
    import sys
    caps = {"SC": 1 << 10, "FC": 256, "TRL": 16, "GAM16": 32, "MSL": 16,
            "VC": 256}
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAXMC_COMPILE_CACHE="off",
               JAXMC_CAP_PROFILE="0", JAXMC_LEDGER="off")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-c", _DRIVE.format(bench=lib.BENCH, caps=caps)],
        cwd=lib.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads([ln for ln in p.stdout.splitlines()
                      if ln.startswith("OUT ")][-1][4:])
    assert out["correct"] and out["attempted"] >= 1
    art = out["artifacts"]
    rise = art["after"]["counters"]["mesh.exchange_bytes"] - \
        art["at_window"]["counters"]["mesh.exchange_bytes"]
    # the toy model: 2 processes -> 7 instances, 7 levels a search, and a
    # state that packs into ONE word (the cell's four processes take two)
    levels = len(art["reference"]["levels"])
    leaving = M.search_bytes_leaving_a_chip(levels, 7, 256, 32, 4, 1, 4)
    assert rise * 3 == leaving * 16 * art["searches"]
    one = {"out": out}
    read = lambda name: lib.load_module(          # noqa: E731
        os.path.join(lib.BENCH, "layers", name + ".py"),
        "bench_layer_" + name).read(one)
    # the integer rise is compared above; the reader's MB is a float
    assert read("exchange_mb_per_search") == pytest.approx(
        leaving * 16 / 3 / 1e6)
    assert read("supersteps_per_search") == 1.0
