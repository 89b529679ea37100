"""Plain reference for bench/specs/transfer_violation.tla: an explicit-state
BFS in numpy that STOPS at a violated invariant, and a judge of behaviours.
Written from the spec's text; imports nothing of jaxmc and nothing of the
other reference (its own copy of the step relation).

The spec EXTENDS transfer_scaled (the tla-rust README's money-transfer race,
N processes):

    Init      alice = MaxMoney, bob = 0, money in [Procs -> 1..MaxMoney],
              pc = [p |-> "check"]
    Check(p)  pc[p] = "check"  -> pc[p]' = alice >= money[p] ? "debit" : "done"
    Debit(p)  pc[p] = "debit"  -> alice' = alice - money[p], pc[p]' = "credit"
    Credit(p) pc[p] = "credit" -> bob' = bob + money[p],     pc[p]' = "done"
    Terminating  all done -> UNCHANGED vars
    Next      \\E p : Check(p) \\/ Debit(p) \\/ Credit(p)  \\/ Terminating

and adds the two things the race breaks:

    AliceBounded    alice <= MaxMoney   (holds)
    AliceSolvent    alice >= 0          (README's assertion; first false
                                         at distance 4)
    NoMoneyCreated  bob <= MaxMoney     (README's MoneyInvariant as a state
                                         invariant: bob never holds more
                                         money than existed; distance 6)

`explore` counts as TLC does and stops as the engines do: the invariants run
over every NEW state of a level after the whole level has been generated, so
`generated` and `distinct` at a violation are whole levels and `diameter` is
the depth of the first level that holds a violating state.

`check_trace` judges ANY behaviour: it knows no search order, only the rules
(Init, a step of Next by the action its label names, the invariant false at
the end and nowhere before, the minimal length).  Which pair of processes
races is the witness's choice; the seed permutes Procs.

A state is one int64: alice (offset so it is >= 0), bob, N money digits,
N pc digits.  `key_bits` narrows the dedup key to its low bits — the CONTROL
of the benchmark's `correct` (bench/control.py).
"""

from __future__ import annotations

import re

import numpy as np

CHECK, DEBIT, CREDIT, DONE = 0, 1, 2, 3

#: every invariant the module defines, over decoded (alice, bob) arrays
INVARIANTS = {
    "AliceBounded": lambda alice, bob, m: alice <= m,
    "AliceSolvent": lambda alice, bob, m: alice >= 0,
    "NoMoneyCreated": lambda alice, bob, m: bob <= m,
}
#: what the cell's cfg checks, in its order (`which` = 1 fails)
CFG_INVARIANTS = ("AliceBounded", "NoMoneyCreated")


def parse_cfg(text: str):
    """(n_procs, max_money, invariants) from a transfer_violation .cfg."""
    text = re.sub(r"\\\*.*", "", text)
    pm = re.search(r"Procs\s*=\s*\{([^}]*)\}", text)
    km = re.search(r"MaxMoney\s*=\s*(\d+)", text)
    if not pm or not km:
        raise ValueError("cfg names no Procs set or no MaxMoney")
    procs = [p.strip() for p in pm.group(1).split(",") if p.strip()]
    if len(set(procs)) != len(procs):
        raise ValueError(f"duplicate process names in {procs}")
    invs = re.findall(
        r"INVARIANTS?\s+((?:\w+\s*)+?)(?=CONSTANTS?|SPECIFICATION|$)", text)
    names = [w for blk in invs for w in blk.split()]
    unknown = [nm for nm in names if nm not in INVARIANTS]
    if unknown:
        raise ValueError(f"the module defines no invariant {unknown}")
    return len(procs), int(km.group(1)), names


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

    def bob(self, s):
        return (s // self.a_rad) % self.b_rad

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
        chk = pc == CHECK
        to = np.where(alice[chk] >= mon[chk], DEBIT, DONE)
        out.append(f[chk] + to * c.pc_w[p])
        deb = pc == DEBIT
        out.append(f[deb] - mon[deb] + (CREDIT - DEBIT) * c.pc_w[p])
        cre = pc == CREDIT
        out.append(f[cre] + mon[cre] * c.a_rad + (DONE - CREDIT) * c.pc_w[p])
    out.append(f[all_done])                            # Terminating
    return np.concatenate(out)


def _violating(c: _Codec, states: np.ndarray, name: str) -> np.ndarray:
    return ~INVARIANTS[name](c.alice(states), c.bob(states), c.m)


def explore(n_procs: int, max_money: int, invariants=CFG_INVARIANTS,
            key_bits: int = 0) -> dict:
    """BFS that stops at the end of the first level holding a state that
    violates one of `invariants` (checked in order; the first that fails
    anywhere in the level is named).  Returns generated, distinct,
    diameter, ok, and per-level rows [frontier, generated, new]; where it
    stopped at a violation also `invariant`, `which` (its index) and
    `violating` (new states of the last level that violate it)."""
    c = _Codec(n_procs, max_money)
    mask = (1 << key_bits) - 1 if key_bits else -1

    def dedup(states, seen_keys):
        keys = states & mask
        keys, first = np.unique(keys, return_index=True)
        fresh = ~np.isin(keys, seen_keys, assume_unique=True)
        return states[first[fresh]], np.union1d(seen_keys, keys[fresh])

    def first_violated(states):
        for which, name in enumerate(invariants):
            bad = int(_violating(c, states, name).sum())
            if bad:
                return {"ok": False, "invariant": name, "which": which,
                        "violating": bad}
        return None

    init = _init_states(c)
    generated = int(init.size)
    frontier, seen = dedup(init, np.empty(0, np.int64))
    distinct, levels, depth = int(frontier.size), [], 0
    verdict = first_violated(frontier)
    while verdict is None:
        succ = _successors(c, frontier)
        generated += int(succ.size)
        new, seen = dedup(succ, seen)
        levels.append([int(frontier.size), int(succ.size), int(new.size)])
        distinct += int(new.size)
        if not new.size:
            verdict = {"ok": True}
            break
        frontier = new
        depth += 1
        verdict = first_violated(new)
    return dict(verdict, generated=generated, distinct=distinct,
                diameter=depth, levels=levels)


# ------------------------------------------------------ judging a trace

def _plain(state, procs):
    """(alice, bob, {p: money}, {p: pc}) of one decoded state, whose
    `money` and `pc` are plain dicts over the process names."""
    money = {str(p): int(v) for p, v in state["money"].items()}
    pc = {str(p): str(v) for p, v in state["pc"].items()}
    if sorted(money) != procs or sorted(pc) != procs:
        raise ValueError(f"money over {sorted(money)}, pc over "
                         f"{sorted(pc)}, Procs {procs}")
    return int(state["alice"]), int(state["bob"]), money, pc


_LABEL = re.compile(r"^(Check|Debit|Credit)\((\w+)\)$|^(Terminating)$")


def _step(before, label: str, m: int):
    """The one successor of `before` by the action `label` names, or a
    string saying why the action is not enabled."""
    alice, bob, money, pc = before
    lm = _LABEL.match(label)
    if not lm:
        return f"label {label!r} names no action of Next"
    if lm.group(3):
        if any(v != "done" for v in pc.values()):
            return "Terminating with a process not done"
        return before
    act, p = lm.group(1), lm.group(2)
    if p not in pc:
        return f"{label}: no such process"
    want = {"Check": "check", "Debit": "debit", "Credit": "credit"}[act]
    if pc[p] != want:
        return f"{label}: pc[{p}] is {pc[p]!r}, not {want!r}"
    pc2 = dict(pc)
    if act == "Check":
        pc2[p] = "debit" if alice >= money[p] else "done"
    elif act == "Debit":
        alice, pc2[p] = alice - money[p], "credit"
    else:
        bob, pc2[p] = bob + money[p], "done"
    return alice, bob, money, pc2


def check_trace(states, labels, n: int, m: int, invariant: str,
                min_len=None):
    """(ok, why) for a behaviour: `states` decoded states ({"alice", "bob",
    "money": {process: int}, "pc": {process: str}}), `labels` one per
    state ("Initial predicate" first, then the action that led to the
    state), `n` the number of processes (their names are the first
    state's, whatever the cfg calls them).  The rules: the first state
    satisfies Init; every consecutive pair is a step of Next by the action
    its label names; the last state violates `invariant` and no earlier
    one does; and, where `min_len` is given (the reference's minimal depth
    + 1), the length is that — BFS returns a shortest counterexample."""
    try:
        if len(states) != len(labels) or not states:
            return False, f"{len(states)} states, {len(labels)} labels"
        if min_len is not None and len(states) != min_len:
            return False, (f"length {len(states)}, the shortest "
                           f"counterexample has {min_len} states")
        procs = sorted(str(p) for p in states[0]["money"])
        if len(procs) != n:
            return False, f"{len(procs)} processes, the cfg has {n}"
        plain = [_plain(s, procs) for s in states]
        alice, bob, money, pc = plain[0]
        if labels[0] != "Initial predicate":
            return False, f"first label {labels[0]!r}"
        if alice != m or bob != 0 or \
                any(not 1 <= v <= m for v in money.values()) or \
                any(v != "check" for v in pc.values()):
            return False, f"the first state does not satisfy Init: {plain[0]}"
        holds = INVARIANTS[invariant]
        for i in range(1, len(plain)):
            want = _step(plain[i - 1], labels[i], m)
            if isinstance(want, str):
                return False, f"step {i}: {want}"
            if want != plain[i]:
                return False, (f"step {i}: {labels[i]} leads to {want}, "
                               f"the trace says {plain[i]}")
        for i, (alice, bob, _, _) in enumerate(plain):
            bad = not holds(np.int64(alice), np.int64(bob), m)
            if bad != (i == len(plain) - 1):
                verb = "violates" if bad else "satisfies"
                return False, (f"state {i} {verb} {invariant}: alice "
                               f"{alice}, bob {bob}")
        return True, "a behaviour of Spec that ends in the violation"
    except (AttributeError, KeyError, TypeError, ValueError) as ex:
        return False, f"malformed trace: {type(ex).__name__}: {ex}"


if __name__ == "__main__":
    import json
    import sys
    import time
    n, m = int(sys.argv[1]), int(sys.argv[2])
    bits = int(sys.argv[3]) if len(sys.argv) > 3 else 0
    t0 = time.time()
    r = explore(n, m, key_bits=bits)
    r["wall_s"] = round(time.time() - t0, 3)
    print(json.dumps(r))
