"""Plain reference for specs/transfer_scaled.tla: an explicit-state BFS in
numpy, written from the spec's text and importing nothing of jaxmc.

The spec (the tla-rust README's money-transfer race, N processes):

    Init      alice = MaxMoney, bob = 0, money in [Procs -> 1..MaxMoney],
              pc = [p |-> "check"]
    Check(p)  pc[p] = "check"  -> pc[p]' = alice >= money[p] ? "debit" : "done"
    Debit(p)  pc[p] = "debit"  -> alice' = alice - money[p], pc[p]' = "credit"
    Credit(p) pc[p] = "credit" -> bob' = bob + money[p],     pc[p]' = "done"
    Terminating  all done -> UNCHANGED vars
    Next      \\E p : Check(p) \\/ Debit(p) \\/ Credit(p)  \\/ Terminating
    invariant AliceBounded  alice <= MaxMoney

Counting follows TLC: `generated` = initial states + every successor
computed from every explored state (duplicates included, the Terminating
stutter too); `distinct` = states reached; `diameter` = depth of the
deepest BFS level (level 0 = Init).  Integers are TLC's: alice may go
negative (that is the race).

A state is one int64: alice (offset so it is >= 0), bob, N money digits,
N pc digits.  `key_bits` narrows the dedup key to its low bits — the
CONTROL of the benchmark's `correct`: a checker whose dedup keys are too
narrow merges distinct states and undercounts.
"""

from __future__ import annotations

import re

import numpy as np

CHECK, DEBIT, CREDIT, DONE = 0, 1, 2, 3


def parse_cfg(text: str):
    """(n_procs, max_money, invariants) from a transfer_scaled .cfg."""
    text = re.sub(r"\\\*.*", "", text)
    m = re.search(r"Procs\s*=\s*\{([^}]*)\}", text)
    k = re.search(r"MaxMoney\s*=\s*(\d+)", text)
    if not m or not k:
        raise ValueError("cfg names no Procs set or no MaxMoney")
    procs = [p.strip() for p in m.group(1).split(",") if p.strip()]
    if len(set(procs)) != len(procs):
        raise ValueError(f"duplicate process names in {procs}")
    invs = re.findall(r"INVARIANTS?\s+((?:\w+\s*)+?)(?=CONSTANTS?|SPECIFICATION|$)",
                      text)
    names = [w for blk in invs for w in blk.split()]
    return len(procs), int(k.group(1)), names


class _Codec:
    def __init__(self, n: int, m: int):
        self.n, self.m = n, m
        self.a_off = (n - 1) * m          # alice >= m - n*m
        self.a_rad = n * m + 1            # alice + a_off in 0..n*m
        self.b_rad = n * m + 1            # bob in 0..n*m
        self.money_w = [self.a_rad * self.b_rad * (m ** i)
                        for i in range(n)]
        base = self.a_rad * self.b_rad * (m ** n)
        self.pc_w = [base * (4 ** i) for i in range(n)]
        if base * (4 ** n) >= 2 ** 62:
            raise ValueError("state does not fit an int64 key")

    def alice(self, s):
        return s % self.a_rad - self.a_off

    def money(self, s, p):
        return (s // self.money_w[p]) % self.m + 1

    def pc(self, s, p):
        return (s // self.pc_w[p]) % 4


def state_bits(n_procs: int, max_money: int) -> int:
    """Bits of the exact state key: a dedup key narrower than this merges
    distinct states."""
    c = _Codec(n_procs, max_money)
    return int(c.pc_w[-1] * 4 - 1).bit_length()


def _init_states(c: _Codec) -> np.ndarray:
    n, m = c.n, c.m
    grids = np.indices((m,) * n).reshape(n, -1)      # money-1 per proc
    s = np.full(grids.shape[1], m + c.a_off, np.int64)   # alice=M, bob=0
    for p in range(n):
        s = s + grids[p].astype(np.int64) * c.money_w[p]
    return s                                           # pc all CHECK (0)


def _successors(c: _Codec, f: np.ndarray) -> np.ndarray:
    out = []
    alice = c.alice(f)
    all_done = np.ones(f.shape, bool)
    for p in range(c.n):
        pc, mon = c.pc(f, p), c.money(f, p)
        all_done &= pc == DONE
        chk = f[pc == CHECK]
        if chk.size:
            to = np.where(alice[pc == CHECK] >= mon[pc == CHECK],
                          DEBIT, DONE)
            out.append(chk + to * c.pc_w[p])
        deb = pc == DEBIT
        out.append(f[deb] - mon[deb] + (CREDIT - DEBIT) * c.pc_w[p])
        cre = pc == CREDIT
        out.append(f[cre] + mon[cre] * c.a_rad + (DONE - CREDIT) * c.pc_w[p])
    out.append(f[all_done])                            # Terminating
    return np.concatenate(out)


def explore(n_procs: int, max_money: int, key_bits: int = 0) -> dict:
    """Exhaustive BFS.  Returns generated, distinct, diameter, ok (the
    invariant held on every distinct state) and per-level rows
    [frontier, generated, new]."""
    c = _Codec(n_procs, max_money)
    mask = (1 << key_bits) - 1 if key_bits else -1

    def dedup(states, seen_keys):
        keys = states & mask
        keys, first = np.unique(keys, return_index=True)
        fresh = ~np.isin(keys, seen_keys, assume_unique=True)
        return states[first[fresh]], np.union1d(seen_keys, keys[fresh])

    init = _init_states(c)
    generated = int(init.size)
    frontier, seen = dedup(init, np.empty(0, np.int64))
    distinct, ok, levels = int(frontier.size), True, []
    depth = 0
    while True:
        ok = ok and bool((c.alice(frontier) <= max_money).all())
        succ = _successors(c, frontier)
        generated += int(succ.size)
        new, seen = dedup(succ, seen)
        levels.append([int(frontier.size), int(succ.size), int(new.size)])
        distinct += int(new.size)
        if not new.size:
            break
        frontier = new
        depth += 1
    return {"generated": generated, "distinct": distinct, "diameter": depth,
            "ok": ok, "levels": levels}


if __name__ == "__main__":
    import json
    import sys
    import time
    n, m = int(sys.argv[1]), int(sys.argv[2])
    bits = int(sys.argv[3]) if len(sys.argv) > 3 else 0
    t0 = time.time()
    r = explore(n, m, bits)
    r["wall_s"] = round(time.time() - t0, 3)
    print(json.dumps(r))
