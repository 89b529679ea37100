"""Shared plumbing of the benchmark harness.  Imports no jax and nothing of
jaxmc: `run.py` and the ci driver must leave the chip to the process that
runs the search (README.md, "Why run.py stays off jax")."""

from __future__ import annotations

import importlib.util
import json
import os
import random
import re
import shutil
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

#: the driver's character set for names and units (contract)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class BenchFailure(Exception):
    """The run cannot give a result: no chip, a demotion, a dead daemon.
    run.py exits non-zero and prints NO result line."""


def need(cond, msg: str) -> None:
    if not cond:
        raise BenchFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: str):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_module(path: str, name: str):
    """A driver / reader / reference found BY FILE NAME (the names carry
    dots and dashes, so they are not importable as packages)."""
    need(os.path.isfile(path), f"no such harness file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, bench_dir: str = BENCH) -> dict:
    """Everything one cell is made of, found by the names in
    BENCHMARK.json: its entry, configuration, mix, pins, driver path and
    the per-layer readers that apply to it."""
    root = os.path.dirname(bench_dir)
    bm = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    need(workload in cells, f"unknown workload {workload!r}; BENCHMARK.json "
                            f"has {sorted(cells)}")
    cell = cells[workload]
    conf_entry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, conf_entry["file"]))
    mix = load_json(os.path.join(bench_dir, "traffic",
                                 cell["traffic"] + ".json"))
    pins = load_json(os.path.join(bench_dir, "pins", mix["pins"] + ".json"))
    e2e = [m for m in bm["end_to_end"]
           if workload in m.get("workloads", cells)]
    e2e_names = {m["name"] for m in e2e}
    layers = [m for m in bm["per_layer"]
              if (workload in m["workloads"] if "workloads" in m
                  else m["moves"] in e2e_names)]
    return {"benchmark": bm, "cell": cell, "config": config, "mix": mix,
            "pins": pins, "end_to_end": e2e, "per_layer": layers,
            "driver_path": os.path.join(bench_dir, "drivers",
                                        mix["driver"] + ".py"),
            "reader_path": lambda name: os.path.join(
                bench_dir, "layers", name + ".py"),
            "bench_dir": bench_dir, "root": root}


def work_dir(workload: str, root: str = ROOT) -> str:
    """The cell's scratch directory: a FIXED path inside the checkout
    (never a temp name, pid or time), emptied at the start of each run."""
    d = os.path.join(root, ".bench_work", workload)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    return d


def child_env(root: str = ROOT, **extra) -> dict:
    env = dict(os.environ, PYTHONPATH=root, JAXMC_LEDGER="off")
    env.update(extra)
    return env


# ------------------------------------------------------------ the seed

_ASSIGN = re.compile(r"^(\s*)(\w+)\s*=\s*(.+?)\s*$")


def permute_cfg(text: str, seed: int) -> str:
    """The traffic generator: the SAME model written differently.  A
    checker's input is a spec and a cfg; the seed may change only what
    leaves the state graph (so the pinned counts) and the compiled
    programs unchanged: the order of the `Name = value` lines under
    CONSTANTS and of the elements inside each `{...}` set.  Everything
    else is copied as it stands."""
    rng = random.Random(seed)
    out, block = [], []

    def flush():
        rng.shuffle(block)
        out.extend(block)
        block.clear()

    in_consts = False
    for line in text.splitlines():
        head = line.strip().split(" ")[0] if line.strip() else ""
        if head in ("CONSTANT", "CONSTANTS"):
            flush()
            in_consts = True
            out.append(line)
            continue
        m = _ASSIGN.match(line)
        if in_consts and m:
            ind, name, val = m.groups()
            if val.startswith("{") and val.endswith("}"):
                elems = [e.strip() for e in val[1:-1].split(",")
                         if e.strip()]
                rng.shuffle(elems)
                val = "{" + ", ".join(elems) + "}"
            block.append(f"{ind}{name} = {val}")
            continue
        if line.strip():
            flush()
            in_consts = False
        out.append(line)
    flush()
    return "\n".join(out) + "\n"


def write_seed_cfg(ctx: dict, work: str):
    """(text, path) of the cfg this run checks: the mix's cfg (at toy size
    in a rehearsal) as the seed writes it, under the cell's work dir."""
    mix = ctx["mix"]
    src = mix["rehearsal_cfg"] if ctx["rehearsal"] else \
        open(os.path.join(ctx["root"], mix["cfg"]), encoding="utf-8").read()
    text = permute_cfg(src, ctx["seed"])
    path = os.path.join(work, os.path.basename(mix["cfg"]))
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(text)
    return text, path


def stamp_spec(text: str, seed: int) -> str:
    """The spec with a comment line naming the seed after its MODULE
    header: a new content hash (so a new job signature on the served
    path), the same module."""
    lines = text.splitlines()
    for i, ln in enumerate(lines):
        if re.match(r"^-{4,}\s*MODULE\s+\w+\s*-{4,}", ln):
            lines.insert(i + 1, f"\\* bench traffic, seed {seed}")
            return "\n".join(lines) + "\n"
    raise BenchFailure("spec has no MODULE header to stamp")


# ------------------------------------------------- correct: the compare

def compare(got: dict, want: dict, label: str) -> bool:
    """The comparison that decides `correct` for one operation (a whole
    search or a job): every number of the program's answer beside the
    plain reference's, each with its limit — 0, the comparison is exact.
    Prints each number compared."""
    ok = True
    for key in ("generated", "distinct", "diameter"):
        g, w = got.get(key), want[key]
        gap = None if g is None else abs(int(g) - int(w))
        good = gap == 0
        say(f"  compare {label} {key}: program {g} reference {w} "
            f"gap {gap} limit 0 {'ok' if good else 'FAILED'}")
        ok = ok and good
    for key, w in (("ok", want["ok"]), ("truncated", False)):
        g = got.get(key)
        good = g is w
        say(f"  compare {label} {key}: program {g} reference {w} "
            f"{'ok' if good else 'FAILED'}")
        ok = ok and good
    return ok


def reference_answer(mix: dict, cfg_text: str, bench_dir: str = BENCH,
                     key_bits: int = 0) -> dict:
    """Run the mix's plain reference on the cfg the program was given."""
    ref = load_module(os.path.join(bench_dir, "reference",
                                   mix["reference"] + ".py"),
                      "bench_reference_" + mix["reference"])
    n, m, invs = ref.parse_cfg(cfg_text)
    need(invs, "cfg checks no invariant")
    return ref.explore(n, m, key_bits=key_bits)


def check_pins(ref: dict, pins: dict) -> None:
    """The reference computed in this run against the pin file (the exact
    interpreter's counts, PR 21): a disagreement means the yardstick
    itself is broken, and no result is printed."""
    for key in ("generated", "distinct", "diameter"):
        need(ref[key] == pins[key],
             f"plain reference {key} {ref[key]} != pin {pins[key]} "
             f"({pins.get('confirmed_by')})")


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    return json.dumps(out)
