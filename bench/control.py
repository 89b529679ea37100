#!/usr/bin/env python3
"""The control of `correct`: a checker whose dedup keys are too narrow.

    python3 bench/control.py --workload <name> --seeds 1,2,3

The benchmark states no arithmetic precision; the guarantee it states is
that dedup loses no state.  The control breaks exactly that: the plain
reference, put in the program's place, with its dedup key narrowed by
`--drop-bits` below the width of the state (default 4) — the step that would
tempt a later PR (a 32-bit fingerprint where 128 are kept, a key that drops
a lane).  Narrow keys merge distinct states, so fewer are explored and both
counts fall.  For every seed it prints the control's answer through the
harness's own comparison and must see it come out NOT correct; it exits 0
only then.  It runs at the cell's own size (numpy, seconds) and needs no
chip; bench/tests runs it at a test's size.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import lib  # noqa: E402


def control_answer(mix: dict, cfg_text: str, drop_bits: int,
                   bench_dir: str = lib.BENCH) -> dict:
    ref = lib.load_module(os.path.join(bench_dir, "reference",
                                       mix["reference"] + ".py"),
                          "bench_reference_" + mix["reference"])
    n, m, _ = ref.parse_cfg(cfg_text)
    return ref.explore(n, m, key_bits=ref.state_bits(n, m) - drop_bits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--drop-bits", type=int, default=4)
    args = ap.parse_args(argv)
    res = lib.resolve(args.workload)
    src = open(os.path.join(res["root"], res["mix"]["cfg"]),
               encoding="utf-8").read()
    caught = 0
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        cfg_text = lib.permute_cfg(src, seed)
        ref = lib.reference_answer(res["mix"], cfg_text)
        lib.check_pins(ref, res["pins"])
        got = control_answer(res["mix"], cfg_text, args.drop_bits)
        got["truncated"] = False
        ok = lib.compare(got, ref, f"control seed {seed}")
        lib.say(f"control: seed {seed} narrowed by {args.drop_bits} bits "
                f"-> correct={ok} (distinct gap "
                f"{ref['distinct'] - got['distinct']}, generated gap "
                f"{ref['generated'] - got['generated']})")
        caught += 0 if ok else 1
    lib.say(f"control: {caught} of {len(seeds)} seeds came out not correct")
    return 0 if caught == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
