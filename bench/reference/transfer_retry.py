"""Plain reference for bench/specs/transfer_retry.tla under the cfg's
`CONSTRAINT TriesBounded`: an explicit-state BFS in numpy, written from the
spec's text.  Imports nothing of jaxmc and nothing of the other references
(its own copy of the step relation).

The spec EXTENDS transfer_scaled (the tla-rust README's money-transfer race,
N processes) and lets a finished transfer be retried, counting the retries:

    InitR     alice = MaxMoney, bob = 0, money in [Procs -> 1..MaxMoney],
              pc = [p |-> "check"], tries = [p |-> 0]
    Check(p)  pc[p] = "check"  -> pc[p]' = alice >= money[p] ? "debit" : "done"
    Debit(p)  pc[p] = "debit"  -> alice' = alice - money[p], pc[p]' = "credit"
    Credit(p) pc[p] = "credit" -> bob' = bob + money[p],     pc[p]' = "done"
    Retry(p)  pc[p] = "done"   -> pc[p]' = "check", tries[p]' = tries[p] + 1
    NextR     \\E p : Check(p) \\/ Debit(p) \\/ Credit(p) \\/ Retry(p)
    invariant  AliceBounded   alice <= MaxMoney
    constraint TriesBounded   \\A p : tries[p] <= MaxTries

Nothing in the spec bounds `tries`: the model is infinite, and the cfg's
CONSTRAINT alone makes the search end.  Every process has exactly one
enabled action in every state (no Terminating stutter: NextR has none, and
needs none — a done process can always retry), so a state has N successors
and none deadlocks.

Counting follows TLC under a CONSTRAINT: a successor that violates the
constraint is GENERATED (it counts in `generated`) and FINGERPRINTED (it
enters the seen set, so that meeting it again costs nothing) and then
DISCARDED: it is not `distinct`, its invariants are not checked and it is
never explored.  So besides `generated` / `distinct` / `diameter` the answer
says how many rows entered the seen set (`fingerprinted`: the initial states
and every new successor, kept or not) and how many of those the constraint
discarded (`discarded`); `fingerprinted - discarded == distinct`.  `levels`
has the three columns every pins file has, [frontier, generated, new], where
`new` counts the rows KEPT (what `distinct` sums and the next frontier
holds); `fingerprinted_levels` has, level for level, the rows that entered
the seen set (what a seen table must seat).  The initial states all satisfy
the constraint (tries = 0).

A state is one int64: alice (offset so it is >= 0), bob, then one digit a
process, (tries * MaxMoney + money - 1) * 4 + pc.  A discarded state has
tries[p] = MaxTries + 1 for exactly one p (one step past the bound, never
further: it is not explored), so tries needs MaxTries + 2 values.
`key_bits` narrows the dedup key to its low bits — the CONTROL of the
benchmark's `correct` (bench/control.py).

`scale` is the pair (MaxMoney, MaxTries): `lib.reference_answer` and
`bench/control.py` hand `parse_cfg`'s second value to `explore` and
`state_bits` as it stands.
"""

from __future__ import annotations

import re

import numpy as np

CHECK, DEBIT, CREDIT, DONE = 0, 1, 2, 3


def parse_cfg(text: str):
    """(n_procs, (max_money, max_tries), invariants) from a transfer_retry
    .cfg.  A cfg without the CONSTRAINT line is another model (an infinite
    one) and is refused."""
    text = re.sub(r"\\\*.*", "", text)
    if not re.search(r"^\s*CONSTRAINTS?\s+TriesBounded\s*$", text, re.M):
        raise ValueError("cfg holds no `CONSTRAINT TriesBounded` line: "
                         "the model is infinite, not this reference's")
    m = re.search(r"Procs\s*=\s*\{([^}]*)\}", text)
    k = re.search(r"MaxMoney\s*=\s*(\d+)", text)
    t = re.search(r"MaxTries\s*=\s*(\d+)", text)
    if not m or not k or not t:
        raise ValueError("cfg names no Procs set, no MaxMoney or no "
                         "MaxTries")
    procs = [p.strip() for p in m.group(1).split(",") if p.strip()]
    if len(set(procs)) != len(procs):
        raise ValueError(f"duplicate process names in {procs}")
    invs = re.findall(
        r"INVARIANTS?\s+((?:\w+\s*)+?)"
        r"(?=CONSTANTS?|SPECIFICATION|CONSTRAINTS?|$)", text)
    names = [w for blk in invs for w in blk.split()]
    return len(procs), (int(k.group(1)), int(t.group(1))), names


class _Codec:
    def __init__(self, n: int, scale):
        m, t = scale
        self.n, self.m, self.t = n, m, t
        # a process debits at most once a try, tries 0..MaxTries
        most = n * m * (t + 1)
        self.a_off = most - m             # alice >= m - most
        self.a_rad = most + 1             # alice + a_off in 0..most
        self.b_rad = most + 1             # bob in 0..most
        self.p_rad = 4 * m * (t + 2)      # (tries*m + money-1)*4 + pc
        self.proc_w = [self.a_rad * self.b_rad * self.p_rad ** i
                       for i in range(n)]
        self.top = self.a_rad * self.b_rad * self.p_rad ** n
        if self.top >= 2 ** 62:
            raise ValueError("state does not fit an int64 key")

    def alice(self, s):
        return s % self.a_rad - self.a_off

    def digits(self, s):
        """[len(s), n] process digits."""
        return np.stack([(s // w) % self.p_rad for w in self.proc_w],
                        axis=1)

    def satisfies(self, s):
        """TriesBounded: every process's tries <= MaxTries."""
        return (self.digits(s) // (4 * self.m) <= self.t).all(axis=1)


def state_bits(n_procs: int, scale) -> int:
    """Bits of the exact state key: a dedup key narrower than this merges
    distinct states."""
    return int(_Codec(n_procs, scale).top - 1).bit_length()


def _init_states(c: _Codec) -> np.ndarray:
    n, m = c.n, c.m
    grids = np.indices((m,) * n).reshape(n, -1)          # money-1 per proc
    s = np.full(grids.shape[1], m + c.a_off, np.int64)   # alice=M, bob=0
    for p in range(n):                                   # pc CHECK, tries 0
        s = s + grids[p].astype(np.int64) * (4 * c.proc_w[p])
    return s


def _successors(c: _Codec, f: np.ndarray) -> np.ndarray:
    out = []
    alice = c.alice(f)
    digits = c.digits(f)
    for p in range(c.n):
        pc, mon = digits[:, p] % 4, (digits[:, p] // 4) % c.m + 1
        chk = pc == CHECK
        to = np.where(alice[chk] >= mon[chk], DEBIT, DONE)
        out.append(f[chk] + to * c.proc_w[p])
        deb = pc == DEBIT
        out.append(f[deb] - mon[deb] + (CREDIT - DEBIT) * c.proc_w[p])
        cre = pc == CREDIT
        out.append(f[cre] + mon[cre] * c.a_rad
                   + (DONE - CREDIT) * c.proc_w[p])
        don = pc == DONE                     # Retry: pc check, tries + 1
        out.append(f[don] + (4 * c.m - DONE) * c.proc_w[p])
    return np.concatenate(out)


def explore(n_procs: int, scale, key_bits: int = 0) -> dict:
    """Exhaustive BFS under the constraint.  Returns generated, distinct,
    diameter, ok (the invariant held on every state kept), levels
    [frontier, generated, new kept], fingerprinted, discarded and
    fingerprinted_levels (module docstring)."""
    c = _Codec(n_procs, scale)
    mask = (1 << key_bits) - 1 if key_bits else -1

    def dedup(states, seen_keys):
        keys = states & mask
        keys, first = np.unique(keys, return_index=True)
        fresh = ~np.isin(keys, seen_keys, assume_unique=True)
        return states[first[fresh]], np.union1d(seen_keys, keys[fresh])

    init = _init_states(c)
    generated = int(init.size)
    frontier, seen = dedup(init, np.empty(0, np.int64))
    assert bool(c.satisfies(frontier).all())
    fingerprinted = distinct = int(frontier.size)
    ok, levels, fp_levels, depth = True, [], [], 0
    while True:
        ok = ok and bool((c.alice(frontier) <= c.m).all())
        succ = _successors(c, frontier)
        generated += int(succ.size)
        new, seen = dedup(succ, seen)        # fingerprinted, kept or not
        kept = new[c.satisfies(new)]         # ... then the constraint
        levels.append([int(frontier.size), int(succ.size), int(kept.size)])
        fp_levels.append(int(new.size))
        fingerprinted += int(new.size)
        distinct += int(kept.size)
        if not kept.size:
            break
        frontier = kept
        depth += 1
    return {"generated": generated, "distinct": distinct,
            "diameter": depth, "ok": ok, "levels": levels,
            "fingerprinted": fingerprinted,
            "discarded": fingerprinted - distinct,
            "fingerprinted_levels": fp_levels}


if __name__ == "__main__":
    import json
    import sys
    import time
    n, m, t = (int(a) for a in sys.argv[1:4])
    bits = int(sys.argv[4]) if len(sys.argv) > 4 else 0
    t0 = time.time()
    r = explore(n, (m, t), bits)
    r["wall_s"] = round(time.time() - t0, 3)
    print(json.dumps(r))
