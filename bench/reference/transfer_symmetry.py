"""Plain reference for bench/specs/transfer_symmetry.tla under the cfg's
`SYMMETRY Perms`: an explicit-state BFS over ORBITS in numpy, written from
the spec's text.  Imports nothing of jaxmc and nothing of the other
references (its own copy of the step relation).

The spec EXTENDS transfer_scaled (the tla-rust README's money-transfer race,
N processes) and adds `Perms == Permutations(Procs)`:

    Init      alice = MaxMoney, bob = 0, money in [Procs -> 1..MaxMoney],
              pc = [p |-> "check"]
    Check(p)  pc[p] = "check"  -> pc[p]' = alice >= money[p] ? "debit" : "done"
    Debit(p)  pc[p] = "debit"  -> alice' = alice - money[p], pc[p]' = "credit"
    Credit(p) pc[p] = "credit" -> bob' = bob + money[p],     pc[p]' = "done"
    Terminating  all done -> UNCHANGED vars
    Next      \\E p : Check(p) \\/ Debit(p) \\/ Credit(p)  \\/ Terminating
    invariant AliceBounded  alice <= MaxMoney

The processes run the same code, so a permutation of Procs maps behaviours
to behaviours: two states are in one orbit exactly when they have the same
alice, the same bob and the same MULTISET of (money[p], pc[p]) pairs.  The
orbit's name here is the state with its pairs sorted; that is this file's
own choice (any one member per orbit gives the same counts) and it calls no
canonicaliser of the program.

Counting follows TLC with SYMMETRY: `generated` = EVERY initial state (all
MaxMoney^N of them, before any is recognised as a duplicate) + every
successor computed from ONE stored member of every orbit reached
(duplicates included, the Terminating stutter too); `distinct` = orbits
reached; `diameter` = depth of the deepest BFS level (level 0 = Init).  The
number of successors of a state is the same for every member of its orbit,
so which member is stored moves no count.

`unreduced_distinct` is the orbit sum: the states the search WITHOUT the
SYMMETRY line would reach, which must equal transfer_scaled.py's `distinct`
wherever both run.

A state is one int64: alice (offset so it is >= 0), bob, N pair digits
(pair = (money - 1) * 4 + pc, so the order of the digits is the order of the
pairs).  `key_bits` narrows the dedup key to its low bits — the CONTROL of
the benchmark's `correct` (bench/control.py).
"""

from __future__ import annotations

import math
import re

import numpy as np

CHECK, DEBIT, CREDIT, DONE = 0, 1, 2, 3


def parse_cfg(text: str):
    """(n_procs, max_money, invariants) from a transfer_symmetry .cfg.
    A cfg without the SYMMETRY line is another model (the unreduced one)
    and is refused."""
    text = re.sub(r"\\\*.*", "", text)
    if not re.search(r"^\s*SYMMETRY\s+Perms\s*$", text, re.M):
        raise ValueError("cfg holds no `SYMMETRY Perms` line: not this "
                         "reference's model")
    m = re.search(r"Procs\s*=\s*\{([^}]*)\}", text)
    k = re.search(r"MaxMoney\s*=\s*(\d+)", text)
    if not m or not k:
        raise ValueError("cfg names no Procs set or no MaxMoney")
    procs = [p.strip() for p in m.group(1).split(",") if p.strip()]
    if len(set(procs)) != len(procs):
        raise ValueError(f"duplicate process names in {procs}")
    invs = re.findall(
        r"INVARIANTS?\s+((?:\w+\s*)+?)(?=CONSTANTS?|SPECIFICATION|SYMMETRY|$)",
        text)
    names = [w for blk in invs for w in blk.split()]
    return len(procs), int(k.group(1)), names


class _Codec:
    def __init__(self, n: int, m: int):
        self.n, self.m = n, m
        self.a_off = (n - 1) * m          # alice >= m - n*m
        self.a_rad = n * m + 1            # alice + a_off in 0..n*m
        self.b_rad = n * m + 1            # bob in 0..n*m
        self.p_rad = 4 * m                # pair = (money-1)*4 + pc
        self.pair_w = [self.a_rad * self.b_rad * self.p_rad ** i
                       for i in range(n)]
        self.top = self.a_rad * self.b_rad * self.p_rad ** n
        if self.top >= 2 ** 62:
            raise ValueError("state does not fit an int64 key")

    def alice(self, s):
        return s % self.a_rad - self.a_off

    def pairs(self, s):
        """[len(s), n] pair digits."""
        return np.stack([(s // w) % self.p_rad for w in self.pair_w],
                        axis=1)

    def sort_pairs(self, s):
        """Every state's orbit name: the same alice and bob, the pair
        digits in descending order of weight sorted ascending."""
        head = s % self.pair_w[0]
        pairs = np.sort(self.pairs(s), axis=1)
        return head + pairs @ np.asarray(self.pair_w, np.int64)


def state_bits(n_procs: int, max_money: int) -> int:
    """Bits of the exact state key: a dedup key narrower than this merges
    distinct orbits."""
    return int(_Codec(n_procs, max_money).top - 1).bit_length()


def _init_states(c: _Codec) -> np.ndarray:
    n, m = c.n, c.m
    grids = np.indices((m,) * n).reshape(n, -1)      # money-1 per proc
    s = np.full(grids.shape[1], m + c.a_off, np.int64)   # alice=M, bob=0
    for p in range(n):
        s = s + grids[p].astype(np.int64) * (4 * c.pair_w[p])  # pc CHECK
    return s


def _successors(c: _Codec, f: np.ndarray) -> np.ndarray:
    out = []
    alice = c.alice(f)
    pairs = c.pairs(f)
    all_done = np.ones(f.shape, bool)
    for p in range(c.n):
        pc, mon = pairs[:, p] % 4, pairs[:, p] // 4 + 1
        all_done &= pc == DONE
        chk = pc == CHECK
        to = np.where(alice[chk] >= mon[chk], DEBIT, DONE)
        out.append(f[chk] + to * c.pair_w[p])
        deb = pc == DEBIT
        out.append(f[deb] - mon[deb] + (CREDIT - DEBIT) * c.pair_w[p])
        cre = pc == CREDIT
        out.append(f[cre] + mon[cre] * c.a_rad
                   + (DONE - CREDIT) * c.pair_w[p])
    out.append(f[all_done])                            # Terminating
    return np.concatenate(out)


def _search(n_procs: int, max_money: int, key_bits: int = 0):
    c = _Codec(n_procs, max_money)
    mask = (1 << key_bits) - 1 if key_bits else -1

    def dedup(states, seen_keys):
        keys = states & mask
        keys, first = np.unique(keys, return_index=True)
        fresh = ~np.isin(keys, seen_keys, assume_unique=True)
        return states[first[fresh]], np.union1d(seen_keys, keys[fresh])

    init = _init_states(c)
    generated = int(init.size)
    frontier, seen = dedup(c.sort_pairs(init), np.empty(0, np.int64))
    reached = [frontier]
    ok, levels, depth = True, [], 0
    while True:
        ok = ok and bool((c.alice(frontier) <= max_money).all())
        succ = _successors(c, frontier)
        generated += int(succ.size)
        new, seen = dedup(c.sort_pairs(succ), seen)
        levels.append([int(frontier.size), int(succ.size), int(new.size)])
        if not new.size:
            break
        reached.append(new)
        frontier = new
        depth += 1
    return c, {"generated": generated,
               "distinct": sum(int(r.size) for r in reached),
               "diameter": depth, "ok": ok, "levels": levels}, reached


def explore(n_procs: int, max_money: int, key_bits: int = 0) -> dict:
    """Exhaustive BFS over orbits.  Returns generated, distinct, diameter,
    ok (the invariant held on every orbit reached: it reads alice alone, so
    it is the same on every member) and per-level rows [frontier (orbits
    expanded), generated, new orbits]."""
    return _search(n_procs, max_money, key_bits)[1]


def unreduced_distinct(n_procs: int, max_money: int) -> int:
    """The orbit sum: what the search without SYMMETRY reaches.  An orbit
    with pair multiplicities k1, k2, ... has n! / (k1! k2! ...) members."""
    c, _, reached = _search(n_procs, max_money)
    fact = np.asarray([math.factorial(k) for k in range(n_procs + 1)],
                      np.int64)
    total = 0
    for states in reached:
        pairs = np.sort(c.pairs(states), axis=1)
        # run lengths of equal neighbours, a run's factorial at its end
        run = np.ones(len(states), np.int64)
        denom = np.ones(len(states), np.int64)
        for p in range(1, n_procs):
            same = pairs[:, p] == pairs[:, p - 1]
            denom *= np.where(same, 1, fact[run])
            run = np.where(same, run + 1, 1)
        denom *= fact[run]
        total += int((fact[n_procs] // denom).sum())
    return total


if __name__ == "__main__":
    import json
    import sys
    import time
    n, m = int(sys.argv[1]), int(sys.argv[2])
    bits = int(sys.argv[3]) if len(sys.argv) > 3 else 0
    t0 = time.time()
    r = explore(n, m, bits)
    if not bits:
        r["unreduced_distinct"] = unreduced_distinct(n, m)
    r["wall_s"] = round(time.time() - t0, 3)
    print(json.dumps(r))
