"""BENCHMARK.json against the files it names, and the proof that a later PR
adds a cell, a mix and a per-layer metric with NEW files and one entry."""

import json
import os
import shutil

import lib

ROOT = lib.ROOT


def _bm():
    return lib.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_every_cell_resolves_to_files_that_exist():
    bm = _bm()
    for w in bm["workloads"]:
        res = lib.resolve(w["name"])
        assert os.path.isfile(res["driver_path"]), res["driver_path"]
        for key in ("spec", "cfg"):
            assert os.path.isfile(os.path.join(ROOT, res["mix"][key]))
        assert os.path.isfile(os.path.join(
            lib.BENCH, "reference", res["mix"]["reference"] + ".py"))
        assert res["end_to_end"] and res["per_layer"]
        assert any(m["name"] == "setup_s" for m in res["end_to_end"])
        assert len(res["end_to_end"]) >= 2
        for m in res["per_layer"]:
            assert os.path.isfile(res["reader_path"](m["name"])), m["name"]
            assert m["moves"] in {e["name"] for e in res["end_to_end"]}


def test_names_and_units_are_in_the_drivers_character_set():
    bm = _bm()
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bm[group]:
            assert lib.NAME_RE.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            if "unit" in e:
                assert lib.UNIT_RE.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
                assert e["source"] in ("device_trace", "program_span",
                                       "program_counter", "host_clock")
            for field in ("why", "layer"):
                if field in e:
                    assert 1 <= len(e[field]) <= 200 and "\n" not in e[field]
    assert len(set(names)) == len(names)
    for w in bm["workloads"]:
        assert lib.NAME_RE.match(w["traffic"]) and w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in bm["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for e in bm["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25
    assert len(json.dumps(bm)) < 64 * 1024
    files = [c["file"] for c in bm["configs"]]
    assert len(set(files)) == len(files)
    for c in bm["configs"]:
        conf = lib.load_json(os.path.join(ROOT, c["file"]))
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert conf["source"] == c["source"]


def test_a_fifth_cell_needs_only_new_files_and_one_entry(tmp_path):
    """Copy bench/ and BENCHMARK.json, ADD a mix, a pins file and a
    per-layer reader, append one workloads entry and one per_layer entry,
    and resolve the new cell; no file that was there is edited."""
    root = tmp_path / "repo"
    shutil.copytree(lib.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    mix = lib.load_json(os.path.join(lib.BENCH, "traffic",
                                     "recheck-3p.json"))
    mix["cfg"] = "bench/specs/transfer_scaled_2p5.cfg"
    mix["pins"] = "transfer_scaled_2p5"
    (root / "bench/traffic/recheck-2p5.json").write_text(json.dumps(mix))
    (root / "bench/specs/transfer_scaled_2p5.cfg").write_text(
        "SPECIFICATION Spec\nINVARIANT AliceBounded\nCONSTANTS\n"
        "  Procs = {p1, p2}\n  MaxMoney = 5\n")
    (root / "bench/pins/transfer_scaled_2p5.json").write_text(json.dumps(
        {"generated": 0, "distinct": 0, "diameter": 0, "res_caps": {}}))
    (root / "bench/layers/searches_in_window.py").write_text(
        "def read(run):\n    return run['out']['artifacts']['searches']\n")
    bm = _bm()
    bm["workloads"].append({"name": "desk-recheck-2p5",
                            "config": "desk-1chip",
                            "traffic": "recheck-2p5", "chips": 1,
                            "why": "a fifth cell, for the test"})
    bm["per_layer"].append({"name": "searches_in_window", "unit": "count",
                            "better": "higher",
                            "source": "program_counter",
                            "layer": "engines", "moves": "states_per_s",
                            "workloads": ["desk-recheck-2p5"]})
    for m in bm["end_to_end"]:
        if m["name"] == "states_per_s":
            m["workloads"].append("desk-recheck-2p5")
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    res = lib.resolve("desk-recheck-2p5", str(root / "bench"))
    assert res["mix"]["cfg"].endswith("2p5.cfg")
    assert os.path.isfile(res["driver_path"])
    assert {m["name"] for m in res["per_layer"]} >= {
        "searches_in_window", "dispatches_per_search"}
    reader = lib.load_module(res["reader_path"]("searches_in_window"),
                             "fifth_reader")
    assert reader.read({"out": {"artifacts": {"searches": 7}}}) == 7
    for p, blob in before.items():
        assert p.read_bytes() == blob, f"{p} was edited"
