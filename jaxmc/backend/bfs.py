r"""Device-resident BFS engine (BACKEND=jax) — SURVEY.md §7.5.

The hot loop reconstructed in SURVEY.md §3.2, as array programs: the frontier
and the seen-set live on the accelerator; one jitted level step expands every
(state x grounded action) pair with vmap, masks disabled instances, and
deduplicates by lexicographic multi-key sort (jax.lax.sort).

Two dedup modes:
  exact  (narrow layouts, W <= FP_THRESHOLD): sort keys are all W state
         lanes — zero collision risk, stronger than TLC.
  fp128  (wide layouts — raft's W is ~1-2k lanes): sort keys are the
         four words of a 128-bit fingerprint of the row (vs TLC's
         64-bit, testout2:261-264); the collision probability is
         reported in the result like TLC reports its estimate.  A key
         basis of at most four words is permuted, not hashed: exact.

Capacities are power-of-two buckets that grow on demand, so jit recompiles
O(log N) times; all shapes inside a step are static (XLA/TPU requirement).
Parent provenance rides the sorts as a non-key operand and is streamed to
host per level for counterexample reconstruction — disable with
store_trace=False for benchmark runs.  The resident engine carries no
provenance through its sorts: with store_trace it logs each level's new
frontier rows on the device and, at a violation, one more dispatch walks
the log back by re-expansion (`_make_trace_walk`).
"""

from __future__ import annotations

import math
import os
import threading
import time
from functools import lru_cache, partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from .. import obs
from ..sem.modules import Model, satisfies_constraints
from ..sem.enumerate import enumerate_init, enumerate_next
from ..sem.eval import TLCAssertFailure, eval_expr, _bool
from ..sem.values import EvalError
from ..engine.explore import CheckResult, Violation
from ..engine.simulate import sample_states
from ..compile.vspec import Bounds, CompileError, ModeError
from ..compile.kernel2 import (KernelCtx, OV_DEMOTED, OV_PACK,
                               build_layout2, compile_action2,
                               compile_predicate2, compile_value2,
                               introspect_kernel)
from ..compile.ground import ground_arm, split_arms

SENTINEL = np.int32(2**31 - 1)
FP_THRESHOLD = 48  # lanes; beyond this, dedup on 128-bit fingerprints
# "bounds inference not yet attempted" marker for the per-model cache
# (the cached report itself may legitimately be None = analysis bailed)
_SENTINEL_NO_REPORT = object()
_POR_UNSET = object()
_SIG_UNSET = object()  # TpuExplorer._program_sig not asked for yet

# resident-mode status codes (one summary scalar per dispatched batch)
ST_CONTINUE = 0     # level budget exhausted, search not finished
ST_DONE = 1         # frontier empty: search complete
ST_INV = 2          # invariant violated (aux: which, row)
ST_DEADLOCK = 3     # deadlocked state (aux: row)
ST_ASSERT = 4       # Assert failed inside an enabled action (aux: row)
ST_TRUNC = 5        # max_states reached
ST_OVF_SEEN = 6     # seen-set capacity: grow SC, redo level
ST_OVF_FRONT = 7    # frontier capacity: grow FCap, redo level
ST_OVF_ACC = 8      # level-accumulator capacity: grow AccCap, redo level
ST_OVF_VC = 9       # per-chunk valid-candidate capacity: grow VC, redo level
ST_OVF_LANES = 10   # a container outgrew its lane capacity: hard abort
ST_OVF_LOG = 11     # state-log capacity (traces kept): grow LogCap, redo level

SYMMETRY_WARNING = (
    "cfg SYMMETRY NOT applied on the jax backend: counts are "
    "unreduced and will exceed the interp/TLC reduced counts")

# fingerprint128's start state (the first hex digits of pi) and the
# rounds of its 128-bit permutation (Chaskey's own count)
_FP_SEED = (0x243F6A88, 0x85A308D3, 0x13198A2E, 0x03707344)
_FP_ROUNDS = 8
# ... and its name in a checkpoint: a seen table's fingerprints are only
# met again by the function that made them
KEY_FN = "chaskey8/xor4"


@lru_cache(maxsize=8)
def _table_program(sharding):
    """The jitted fill behind `TpuExplorer._device_table`: one program
    per (table shape, fill, head shape), so fill and head rows write ONE
    table-sized buffer (the update is in place; an eager `.at[].set` on
    a filled table is a second table and a whole-table copy).  Under a
    `sharding` each device fills its own shard of the output."""
    def table(head, shape, fill):
        out = jnp.broadcast_to(jnp.asarray(fill, jnp.int32), shape)
        if head is None:
            return out
        return lax.dynamic_update_slice(out, head, (0,) * len(shape))
    return jax.jit(table, static_argnames=("shape", "fill"),
                   out_shardings=sharding)


def _init_walks(tel) -> int:
    """Enumerations of an Init that `tel` has counted
    (`sem/enumerate.py::enumerate_init`); 0 on the null recorder."""
    return getattr(tel, "counters", {}).get("init.enumerations", 0)


def filter_init_states(model, layout, init_rows):
    """Apply TLC's CONSTRAINT-discard semantics to encoded init rows:
    returns (explored_indices, (invariant_name, state) | None). Violating
    inits are fingerprinted by the caller but never counted distinct,
    invariant-checked, or explored; invariants run on kept inits only
    (host-side interpreter, a decode and an evaluation a DISTINCT row:
    1,728 to 20,736 rows in the transfer cfgs at three and four
    processes, and under SYMMETRY at five the 4,368 orbits of 248,832
    initial states)."""
    from ..sem.modules import satisfies_constraints
    from ..sem.eval import eval_expr, _bool
    explored = []
    for i, row in enumerate(init_rows):
        st = layout.decode(row)
        if not satisfies_constraints(model, st):
            continue
        ctx = model.ctx(state=st)
        for nm, ex in model.invariants:
            if not _bool(eval_expr(ex, ctx), f"invariant {nm}"):
                return explored, (nm, st)
        explored.append(i)
    return explored, None


def _any_fast(x) -> bool:
    """bool(any(x)) without lifting a HOST array onto the device: the
    batched host_seen loop receives numpy step outputs (the vmapped
    dispatcher fetches one packed block a superstep and a member's
    outputs are numpy views into it, backend/batch.py), and an eager
    jnp.any on those pays a host->device->host round trip PER CALL,
    which at thousands of supersteps dominated the batch win."""
    if isinstance(x, np.ndarray):
        return bool(np.any(x))
    return bool(jnp.any(x))


def _pow2_at_least(n: int, lo: int = 256) -> int:
    c = lo
    while c < n:
        c *= 2
    return c


def _take_rows_fast(x, idx) -> np.ndarray:
    """Row-gather returning numpy: fancy-index for host arrays, device
    jnp.take (avoids transferring the full block) for device arrays.

    The index is padded to a power of two (row 0, cut off again on the
    host): an eager `jnp.take` is one XLA program PER INDEX LENGTH, and
    the host_seen loop's lengths are its chunks' new-row counts — up to
    PR 38 a solo job compiled, or loaded from the persistent cache, two
    programs a chunk: 292 and 530 for the two primer jobs of the cell
    `ci-cohort-4p`, 79 s of XLA on a chip where 68 of them missed the
    cache (PERF.md section 6, PR 39)."""
    if isinstance(x, np.ndarray):
        return x[idx]
    n = len(idx)
    pad = np.zeros(_pow2_at_least(n), np.int32)
    pad[:n] = idx
    return np.asarray(jnp.take(x, jnp.asarray(pad), axis=0))[:n]


def _rotl(x, r: int):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def _fp_permute(v0, v1, v2, v3):
    """Chaskey's permutation of four 32-bit words (Mouha et al., SAC
    2014): adds, rotations and xors alone, each step invertible, so the
    whole is a BIJECTION of the 128 bits."""
    for _ in range(_FP_ROUNDS):
        v0 = v0 + v1
        v1 = _rotl(v1, 5) ^ v0
        v0 = _rotl(v0, 16)
        v2 = v2 + v3
        v3 = _rotl(v3, 8) ^ v2
        v0 = v0 + v3
        v3 = _rotl(v3, 13) ^ v0
        v2 = v2 + v1
        v1 = _rotl(v1, 7) ^ v2
        v2 = _rotl(v2, 16)
    return v0, v1, v2, v3


def fingerprint128(rows):
    """rows [N, W] i32 -> [N, 4] i32: a 128-bit state absorbs the row
    four words at a time (xor) and is permuted after each four.

    A row of at most four words — every bit-packed layout of the
    benchmark's cells — is xored into the state ONCE and permuted, in
    straight-line code: the key is a bijection of the row and dedup on
    it is EXACT.  A wider row is absorbed by a scan over its blocks of
    four (the whole permutation after each, so a difference in one
    block cannot be met by one in the next; one loop body whatever the
    width: unrolled, XLA:CPU took 20 s for 32 words and rising).

    What this replaced (ISSUE 51) ran four FNV-style lanes, h = (h ^
    w * m1) * m2 a word, each closed by a bijective finaliser.  A
    product carries a difference UPWARD only, so two rows that differ
    in the top bits of their words alone differed in the top bits of
    each lane alone: `desk-constraint-4p` holds two states (pc[p2],
    tries[p4]; bits 26-31 of the two packed words) whose four lanes all
    met, one state of 8,320,026 was taken for seen, and of its
    12,929,810 rows 10,938 pairs met in the first two lanes, where 64
    honest bits give none."""
    u = rows.astype(jnp.uint32)
    n, width = rows.shape
    v = tuple(jnp.full(n, s, jnp.uint32) for s in _FP_SEED)
    if width <= 4:
        v = _fp_permute(*(v[j] ^ u[:, j] if j < width else v[j]
                          for j in range(4)))
    else:
        nb = -(-width // 4)
        # [nb, 4, N]: a block's four words as rows, N minor
        blocks = jnp.pad(u, ((0, 0), (0, 4 * nb - width))).T \
            .reshape(nb, 4, n)

        def absorb(v, blk):
            return _fp_permute(*(v[j] ^ blk[j] for j in range(4))), None
        v, _ = lax.scan(absorb, v, blocks)
    return jnp.stack(v, axis=1).astype(jnp.int32)


# The shape of the seen-table probe (ISSUE 27), from its tail measured
# alone at the benchmark cells' shapes on the TPU v5e (PERF.md §6, PR
# 27; ms a search's worth of calls, the resident 4-process cell): the
# fixed-trip whole-capacity search 3,113; valid blocks of N/16 rows for
# bit_length(seen_count) rounds 562; blocks of N/64 486 (N/32 510);
# with every 16th sorted query searched first 210 (every 64th 217,
# every 256th 251).  Blocks under 2^12 rows bought nothing (3-process
# cell: 2^12 28.1, 2^11 27.5).  The tests lower the floor and the
# stride to cut toy shapes into several blocks and groups.
#
# What a block of SORTED queries does since ISSUE 45, where the table
# has more rows than _probe_window_rows(SC): its first query and its
# last live one are searched in full against the whole table; if their
# answers lie under W rows apart, the window of the table between them
# is copied out and the block's samples, narrow searches and final
# gather read the copy (_PROBE_WINDOW_ROWS says why); if not, the block
# runs against the whole table as before.  One lax.cond a block.
_PROBE_BLOCK_MIN = 1 << 12
_PROBE_SAMPLE = 16


def _probe_block_rows(n: int) -> int:
    """QB: the rows of one query block of _seen_probe, a static
    function of the query shape alone — a sixty-fourth of the N slots,
    never under _PROBE_BLOCK_MIN nor over N."""
    return min(n, max(_PROBE_BLOCK_MIN, -(-n // 64)))


def _probe_blocks(n_live, n: int):
    """How many query blocks _seen_probe searches for n_live live
    queries of n slots: ceil(n_live / QB).  The ONE block rule: the
    kernel bounds its loop with it on a traced count and the engines
    count `search.slots_probed` with it (Python ints work too)."""
    qb = _probe_block_rows(n)
    return (n_live + (qb - 1)) // qb


def _lower_bound(table, count, queries, cap, lo=None, hi=None):
    """Vectorized lexicographic lower bound: for each query row (i32
    words, signed order) the first index in table[0:count] whose row is
    not less than the query. table [cap, w]: sorted valid prefix of
    length count (traced).  lo / hi [n], given together, promise each
    query's answer lies in [lo, hi]; the default is [0, count].  A
    binary search of bit_length(widest interval) rounds — the fewest
    that close it, so a table of thousands of rows costs 12 rounds and
    not the ceil(log2(cap)) + 1 its capacity would, and a query between
    two neighbours' answers a handful — each round one gather and
    selects (no sort comparators), safe inside while loops.
    _seen_probe hands it one block of queries at a time.

    The search steps MUST be a lax loop, not a Python unroll:
    unrolled, XLA's fusion pass duplicates the whole dependent
    gather/compare chain into every consumer (measured: 1 700+ copies of
    the [cap,w] gather in the optimized HLO, turning a ms-scale level
    step into minutes)."""
    n = queries.shape[0]

    def step(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        row = jnp.take(table, jnp.clip(mid, 0, cap - 1), axis=0)
        lt = jnp.zeros(n, bool)
        gt = jnp.zeros(n, bool)
        for j in range(table.shape[1]):
            undec = ~(lt | gt)
            lt = lt | (undec & (row[:, j] < queries[:, j]))
            gt = gt | (undec & (row[:, j] > queries[:, j]))
        go = lo < hi
        lo = jnp.where(go & lt, mid + 1, lo)
        hi = jnp.where(go & ~lt, mid, hi)
        return lo, hi

    if hi is None:
        widest = jnp.asarray(count, jnp.int32)
        hi = jnp.broadcast_to(widest, (n,))
        # zeros of hi's TYPE: under a vma-checked shard_map a jnp.zeros
        # carry enters the loop unvarying and leaves it device-varying,
        # which fori_loop refuses
        lo = hi - hi
    else:
        widest = jnp.max(hi - lo)
    lo, _ = lax.fori_loop(0, 32 - lax.clz(widest), step, (lo, hi))
    return lo


def _lsd_sort(key_cols, extra_cols):
    """Stable multi-key sort as chained STABLE single-key passes (LSD
    radix over the key words, least-significant first).  Equivalent to
    `lax.sort(key_cols + extra_cols, num_keys=len(key_cols))` with
    key_cols[0] most significant — but multi-key sort comparators
    explode XLA compile time inside while loops, and the single-chip
    resident engine runs this under lax.while_loop.

    The passes are ONE lax.sort in a lax.fori_loop (ISSUE 42): the
    columns ride the carry with the pass's key first, and each turn
    hands them on rotated, so that the next key leads and after the
    last pass every column is back in its place.  XLA:TPU's compile
    seconds follow the sort INSTRUCTIONS of a program (3-5 s each at
    2^20 slots and over: PERF.md §6, PR 42), and _rank_merge holds one
    such chain a rung of its ladder.  Returns the (key_cols,
    extra_cols) lists co-sorted."""
    nk = len(key_cols)
    cols = tuple(reversed(key_cols)) + tuple(extra_cols)

    def one_pass(_, cols):
        res = lax.sort(cols, num_keys=1, is_stable=True)
        return tuple(res[1:nk]) + (res[0],) + tuple(res[nk:])

    cols = lax.fori_loop(0, nk, one_pass, cols)
    return list(reversed(cols[:nk])), list(cols[nk:])


# The rows of the seen table one block of SORTED queries searches
# (ISSUE 45).  The resident engine's keys are 128-bit fingerprints, so a
# block of QB ascending queries answers into QB x seen_count / n_live
# consecutive rows of the table, and on the TPU v5e a gather is cheap
# only from an operand that fits the compiler's fast memory: a probed
# row cost 46.5 ns while the table's four key words (67 MB at SC 2^22)
# sat there and 167.7 ns at SC 2^24 (268 MB), where they cannot
# (ledger, PR 44; PERF.md section 5).  So a block whose answers span
# under W rows cuts that window out of the table and searches the copy.
# The probe alone at desk-deep-4p's shapes, a call a level with the
# pinned counts (s a search's worth; my chip runs, PR 45, PERF.md
# section 6): the whole table 3.668; W 2^19 1.177, 2^20 1.179 (177 of
# 190 blocks), 2^21 1.094 (185), 2^22 1.085 (188) — all four copies sit
# in fast memory.  The whole cell chose between them: 2^21 4,429,925
# states/s, 2^22 3,921,980, 2^20 3,856,735, 2^19 3,860,847 against the
# parent's 2,887,434; at every W but 2^21 the merge's BUILD, whose loop
# body is the same text in all five programs, ran 1.4 x slower (PERF.md
# section 7: placement, not the window).  The tests lower it to give
# toy tables a window.
_PROBE_WINDOW_ROWS = 1 << 21


def _probe_window_rows(sc: int) -> int:
    """W: the rows of the window of the seen table that one block of
    sorted queries searches, a static function of the table's capacity
    alone.  W == sc means no window: the program is the one it was."""
    return min(sc, _PROBE_WINDOW_ROWS)


# The most key slots whose sorted keys _rank_merge's build gathers from
# WHOLE (ISSUE 48).  A block of the build takes its new rows from the
# level's sorted keys, and on the TPU v5e that gather is cheap only
# while its operand fits the compiler's fast memory: a built row cost
# 4.9 ns at N 2^21 (desk-recheck-4p8, [2^21, 5] sat there) and 17.6-27.1
# at N 2^23 (168 MB, HBM; ledger, PR 47).  Over this many slots the
# level's new keys are compacted once and a block reads a B-row window
# of them (_rank_merge says how); at or under it the program is the one
# it was.  The tests lower it to give toy shapes the window.
_BUILD_WHOLE_KEYS = 1 << 21
# the layout the seen table is held to in that form: dim 0 minor, {0,1}
# in XLA's notation — the one it arrives in (_rank_merge says why)
_WORDS_MAJOR = Layout(major_to_minor=(1, 0))


def _build_form(n: int) -> str:
    """Where a block of _rank_merge's build takes its new rows from, a
    static function of the key slots alone (gauge merge.build_form):
    "window", a slice of the level's compacted new keys, or "whole",
    the sorted keys."""
    return "window" if n > _BUILD_WHOLE_KEYS else "whole"


@jax.named_scope("jaxmc.merge.probe")
def _probe_by_block(seen, seen_count, keys, SC, n_live, sorted_keys):
    """_seen_probe, and beside its answers the number of blocks that
    searched a window of the table (None where the probe has none:
    unsorted keys, or a table of no more than W rows)."""
    n = keys.shape[0]
    words = keys[:, 1:]
    seen_words = seen[:, 1:]
    kw = words.shape[1]
    qb = _probe_block_rows(n)
    W = _probe_window_rows(SC)
    windowed = sorted_keys and W < SC
    live = n if n_live is None else jnp.minimum(n_live, n)
    every = _PROBE_SAMPLE
    # rows of a block searched in full first: every `every`-th, the last
    at_s = np.append(np.arange(0, qb, every), qb - 1)

    def whole(q, at):
        if sorted_keys:
            lb_s = _lower_bound(seen_words, seen_count, q[at_s], SC)
            lb_s = jnp.where(at + at_s < live, lb_s, seen_count)
            lb_b = _lower_bound(seen_words, seen_count, q, SC,
                                jnp.repeat(lb_s[:-1], every)[:qb],
                                jnp.repeat(lb_s[1:], every)[:qb])
        else:
            lb_b = _lower_bound(seen_words, seen_count, q, SC)
        at_lb = jnp.take(seen_words, jnp.clip(lb_b, 0, SC - 1), axis=0)
        found_b = (lb_b < seen_count) & jnp.all(at_lb == q, axis=1)
        return found_b, lb_b

    def in_window(q, at, w_lo, w_hi, w0):
        # the same searches over table rows [w0, w0 + W), every rank
        # counted from w0; the samples lie between the block's two ends
        window = lax.dynamic_slice(seen_words, (w0, 0), (W, kw))
        lo, hi = w_lo - w0, w_hi - w0
        lb_s = _lower_bound(window, None, q[at_s], W,
                            jnp.broadcast_to(lo, at_s.shape),
                            jnp.broadcast_to(hi, at_s.shape))
        lb_s = jnp.where(at + at_s < live, lb_s, hi)
        lb_b = _lower_bound(window, None, q, W,
                            jnp.repeat(lb_s[:-1], every)[:qb],
                            jnp.repeat(lb_s[1:], every)[:qb])
        at_lb = jnp.take(window, jnp.clip(lb_b, 0, W - 1), axis=0)
        lb_b = lb_b + w0
        found_b = (lb_b < seen_count) & jnp.all(at_lb == q, axis=1)
        return found_b, lb_b

    def block(b, out):
        found, lb = out[:2]
        at = jnp.minimum(b * qb, n - qb)
        q = lax.dynamic_slice(words, (at, 0), (qb, kw))
        took = ()
        if windowed:
            # the block's first query and its last LIVE one, searched in
            # full: every live answer lies between theirs.  The rows
            # past n_live are not trusted to ascend and take no part
            last = jnp.clip(live - 1 - at, 0, qb - 1)
            ends = jnp.concatenate(
                [q[:1], lax.dynamic_slice(q, (last, 0), (1, kw))])
            w = _lower_bound(seen_words, seen_count, ends, SC)
            w0 = jnp.clip(w[0], 0, SC - W)
            # ... and fits where the row AT the last answer is inside
            fits = w[1] < w0 + W
            found_b, lb_b = lax.cond(
                fits, lambda: in_window(q, at, w[0], w[1], w0),
                lambda: whole(q, at))
            took = (out[2] + fits.astype(jnp.int32),)
        else:
            found_b, lb_b = whole(q, at)
        return (lax.dynamic_update_slice(found, found_b, (at,)),
                lax.dynamic_update_slice(lb, lb_b, (at,))) + took

    # zeros of the keys' TYPE, as _lower_bound's lo: the carry leaves
    # the loop device-varying under shard_map
    lb0 = words[:, 0] - words[:, 0]
    out = lax.fori_loop(0, _probe_blocks(live, n), block,
                        (lb0 != 0, lb0) + ((lb0[0],) if windowed else ()))
    return out if windowed else out + (None,)


def _seen_probe(seen, seen_count, keys, SC, n_live=None,
                sorted_keys=False):
    """Membership of each key row in the seen table's sorted valid
    prefix — the newness verdict the rank-merge computes, exposed
    standalone so the device POR filter (ISSUE 18) can reuse it with
    zero extra dispatches.

    The work follows the rows that exist (ISSUE 27): the N query slots
    are cut into blocks of QB = _probe_block_rows(N) rows and only the
    first _probe_blocks(n_live, N) of them are searched — a lax loop
    with that traced bound, each turn the searches, the gather of the
    rows found and their compare over QB rows, written into the result
    by dynamic_update_slice.  n_live (traced) promises that every row a
    caller will read sits in keys[0:n_live] (76-93 % of the benchmark
    cells' slots are padding: PERF.md §5); the default, N, searches
    every block.  Rows of blocks never run report found False and lb
    0.  The last block starts at N - QB where QB does not divide N, so
    it may search rows of its neighbour again: the same answers.

    keys need NOT be sorted (_lower_bound binary-searches per query);
    invalid rows (validity lane != 0, SENTINEL words) sort past the
    prefix and report False.  sorted_keys=True promises that the data
    words of keys[0:n_live] ascend, as _rank_merge's sorted keys do.
    Lower bounds are then monotone, so a block first searches every
    _PROBE_SAMPLE-th query (and its last) in full, and then every query
    between its two sampled neighbours' answers only — 5-8 rounds over
    the QB rows instead of 18-21.  Rows past n_live are not trusted to
    ascend: a sample among them answers seen_count.

    Monotone answers also mean that a block touches ONE range of the
    table, from its first query's answer to its last live query's.
    Where the table has more than W = _probe_window_rows(SC) rows
    (ISSUE 45), a sorted block searches those two queries in full
    first, and if the range is under W rows it copies that window of
    the table (lax.dynamic_slice, clamped to the table's end) and runs
    the samples, the narrow searches and the final gather against the
    copy, ranks shifted by the window's start and back: one lax.cond a
    block, the other branch the search of the whole table.  Every row
    of keys[0:n_live] gets the answer it had; the rows past n_live of
    a windowed block get a rank inside the window instead of
    seen_count, and nobody reads them.  With W >= SC, or unsorted
    keys, there is no window and no branch.

    Returns (found [N] bool, lb [N] int32 lower-bound rank)."""
    return _probe_by_block(seen, seen_count, keys, SC, n_live,
                           sorted_keys)[:2]


@jax.named_scope("jaxmc.expand")
def _por_mask(found, cvalid, inst_arm, arm_safe, A, FC):
    """Device persistent-set filter (ISSUE 18): per frontier slot f,
    pick the FIRST por-safe arm whose successor set is nonempty and
    entirely NEW — the interp's singleton-ample rule
    (engine/explore._por_expand, first arm in sorted(por_safe) order)
    — and mask every other arm's candidates for that slot; slots with
    no such arm keep full expansion.

    found/cvalid are [C = A*FC] over the dense candidate grid with
    c = a * FC + f; inst_arm [A] maps instance rows to split-arm
    indices (slotted kernels contribute n_slots rows per arm);
    arm_safe [n_arms] marks the arms the independence report proved
    globally-commuting + property-invisible.

    Soundness of probing the PRE-LEVEL seen snapshot: after level L's
    merge the table holds the closure through depth L+1, so a
    successor that probes NEW has strictly greater depth than its
    source — ample chains strictly deepen and every cycle retains a
    fully-expanded state (the BFS cycle proviso C3).  Within-level
    sibling duplicates pass the probe but are deduped by the merge,
    which only makes the filter more conservative, never unsound.
    Deadlock/assert verdicts are evaluated by callers on the PRE-mask
    enabledness, and the ample arm commutes with every arm, so
    invariant/deadlock verdicts match the unreduced run.

    Returns (keep [C] = cvalid minus masked candidates,
             n_ample  frontier slots reduced to a singleton arm,
             n_expanded  frontier slots with any enabled candidate)."""
    n_arms = arm_safe.shape[0]
    cv = cvalid.reshape(A, FC)
    bad = (found & cvalid).reshape(A, FC)
    one_hot = (jnp.arange(n_arms, dtype=jnp.int32)[:, None]
               == inst_arm[None, :]).astype(jnp.int32)   # [n_arms, A]
    en_cnt = one_hot @ cv.astype(jnp.int32)              # [n_arms, FC]
    bad_cnt = one_hot @ bad.astype(jnp.int32)
    elig = arm_safe[:, None] & (en_cnt > 0) & (bad_cnt == 0)
    has = jnp.any(elig, axis=0)                          # [FC]
    # argmax over bool returns the FIRST True: the lowest-indexed
    # eligible arm, matching the interp's sorted(por_safe) order
    chosen = jnp.argmax(elig, axis=0).astype(jnp.int32)
    keep_inst = (~has)[None, :] | \
        (inst_arm[:, None] == chosen[None, :])           # [A, FC]
    keep = keep_inst.reshape(A * FC) & cvalid
    slot_en = jnp.any(cv, axis=0)
    n_ample = jnp.sum(has & slot_en, dtype=jnp.int32)
    n_expanded = jnp.sum(slot_en, dtype=jnp.int32)
    return keep, n_ample, n_expanded


def _por_mask_np(found, cvalid, inst_arm, arm_safe, A, FC):
    """NumPy twin of _por_mask for the host_seen engine's host-side
    filter (same ample rule against the native fingerprint store)."""
    n_arms = arm_safe.shape[0]
    cv = cvalid.reshape(A, FC)
    bad = (found & cvalid).reshape(A, FC)
    one_hot = (np.arange(n_arms)[:, None] == inst_arm[None, :])
    en_cnt = one_hot.astype(np.int64) @ cv.astype(np.int64)
    bad_cnt = one_hot.astype(np.int64) @ bad.astype(np.int64)
    elig = arm_safe[:, None] & (en_cnt > 0) & (bad_cnt == 0)
    has = np.any(elig, axis=0)
    chosen = np.argmax(elig, axis=0)
    keep_inst = (~has)[None, :] | (inst_arm[:, None] == chosen[None, :])
    keep = keep_inst.reshape(A * FC) & cvalid
    slot_en = np.any(cv, axis=0)
    n_ample = int(np.sum(has & slot_en))
    n_expanded = int(np.sum(slot_en))
    return keep, n_ample, n_expanded


# Rows of seen2 that _rank_merge builds at a time, and so the rows of
# the seen table one block gathers from.  Only the blocks that hold a
# live row are built (ISSUE 29), so a block has to be small against the
# table; and on the TPU v5e the gathers are cheap only from a small
# window: a jnp.take of 2^22 distinct rows from the whole [2^22, 5]
# table (84 MB) took 26 ns a row, from [2^20, 5] windows of it 3.5 ns
# (my chip runs, PR 25).  The merge's tail measured alone at the four
# benchmark cells' shapes, a call a level with the pinned counts (ms a
# search's worth; my chip runs, PR 29, PERF.md §6): all blocks of 2^20
# rows 766 / 96 / 319 / 189 (the form up to PR 28); live blocks of 2^20
# 265 / 252 / 119 / -, 2^18 337 / 89 / 50 / 185, 2^17 324 / 51 / 23 /
# 119, 2^16 185 / 18.1 / 18.7 / 97, 2^15 181 / 15.6 / 16.9 / 91 —
# not monotone (2^17 and 2^18 are slower than 2^19 at SC 2^22), so one
# size that was best at every shape, not a share of SC.  A block's NEW
# rows were gathered from all N sorted keys up to PR 47 — the 26 ns case
# above at N 2^23 — and come from a B-row window of the compacted new
# keys since (ISSUE 48, _BUILD_WHOLE_KEYS).  The tests lower it to cut
# toy tables into several blocks.
_MERGE_BLOCK_ROWS = 1 << 15


def _merge_block_rows(sc: int) -> int:
    """B: the rows of one block of _rank_merge's seen2 build, a static
    function of the table's capacity alone."""
    return min(sc, _MERGE_BLOCK_ROWS)


def _merge_blocks(seen_count2, sc: int):
    """How many blocks of seen2 _rank_merge builds for a table of sc
    slots that holds seen_count2 rows after the merge (the true need:
    past sc every block is built): ceil(min(seen_count2, sc) / B).  The
    ONE block rule: the kernel bounds its loop with it on a traced
    count and the engines count `search.slots_merged` with it (Python
    ints work too)."""
    b = _merge_block_rows(sc)
    blocks = (seen_count2 + (b - 1)) // b
    most = -(-sc // b)
    if isinstance(blocks, (int, np.integer)):
        return min(int(blocks), most)
    return jnp.minimum(blocks, most)


# The smallest rung of _rank_merge's sort ladder (ISSUE 42).  A level's
# candidates sit in a prefix of the N key slots (13-24 % of them hold a
# key in the benchmark's resident cells: PERF.md §5), and a sort's cost
# follows the slots it is given, so the keys are sorted over the
# smallest of a static ladder of prefixes that holds them: N, N/2, N/4
# ... down to this floor.  Every rung is one more lax.sort for XLA to
# compile (_lsd_sort's passes are a loop; PERF.md §6, PR 42 has the
# seconds: +3 s cold for the six rungs under 2^21, +11 s for the eight
# under 2^23), so the ladder stops where a rung saves microseconds.
# The tests lower it to cut toy shapes into several rungs.
_SORT_RUNG_MIN = 1 << 15


def _sort_rungs(n: int) -> Tuple[int, ...]:
    """The ladder: prefixes of n key slots _rank_merge may sort instead
    of all n, descending from n by halves while a half is no shorter
    than _SORT_RUNG_MIN.  A static function of the key shape alone."""
    rungs = [n]
    while rungs[-1] // 2 >= max(_SORT_RUNG_MIN, 1):
        rungs.append(rungs[-1] // 2)
    return tuple(rungs)


def _sort_rung_index(n_prefix, n: int):
    """Which rung of _sort_rungs(n) sorts a level whose valid keys sit
    in keys[0:n_prefix]: the smallest that holds them (the whole n past
    it).  The ONE rung rule: the kernel switches on it with a traced
    count and the engines count `search.slots_sorted` with it (Python
    ints work too)."""
    rungs = np.asarray(_sort_rungs(n))
    if isinstance(n_prefix, (int, np.integer)):
        return max(int(np.sum(rungs >= n_prefix)) - 1, 0)
    return jnp.maximum(
        jnp.sum(jnp.asarray(rungs) >= n_prefix, dtype=jnp.int32) - 1, 0)


def _sort_unit(n: int) -> int:
    """The slots the rungs of n are whole multiples of (the smallest
    rung where n is a power of two): a dispatch's loop carry counts
    sorted slots in this unit, as it counts blocks and not slots for
    the probe and the build, so hundreds of levels fit an int32."""
    return math.gcd(*_sort_rungs(n))


def _compact_block_rows(acc_cap: int, fcap: int) -> int:
    """RB: the rows of one block of the resident level's compaction
    gathers (ISSUE 46), a static function of the program's shapes alone
    — the block _rank_merge wrote nk_sidx in, _probe_block_rows of the
    AccCap index slots, never over the FCap rows of the frontier."""
    return min(_probe_block_rows(acc_cap), fcap)


def _compact_blocks(n, cap: int, rb: int):
    """How many blocks of rb rows _gather_prefix runs to put n rows
    into a buffer of cap: ceil(min(n, cap) / rb).  The ONE block rule:
    the kernel bounds its loop with it on a traced count and the engine
    counts `search.slots_compacted` with it (Python ints work too)."""
    if isinstance(n, (int, np.integer)):
        return (min(int(n), cap) + (rb - 1)) // rb
    return (jnp.minimum(n, cap) + (rb - 1)) // rb


def _gather_prefix(rows, idx, n, cap: int, rb: int, zero=None):
    """(out, blocks): out [cap, w] is rows[idx[i]] for i < min(n, cap),
    SENTINEL from there on — the compaction that follows the rows that
    exist (ISSUE 46): a lax loop over blocks of rb indices, bounded on
    the traced count as _rank_merge's index_block and _probe_by_block
    are, and `blocks` the turns it ran (_compact_blocks).  idx reads 0
    past n and XLA cannot know: a jnp.take over all of idx fetches row
    0 for every slot that holds no row.  The last block starts at
    cap - rb where rb does not divide cap and writes its neighbour's
    rows again: the same values.  `zero`, where given, is a 0 of the
    operands' TYPE that the carry starts from (_rank_merge's: under
    shard_map the loop leaves it device-varying)."""
    w = rows.shape[1]
    hi = rows.shape[0] - 1
    blocks = _compact_blocks(n, cap, rb)
    n = jnp.minimum(n, cap)
    out0 = jnp.full((cap, w), SENTINEL, jnp.int32)
    if zero is not None:
        out0 = out0 + zero

    def block(b, out):
        at = jnp.minimum(b * rb, cap - rb)
        got = jnp.take(
            rows, jnp.clip(lax.dynamic_slice(idx, (at,), (rb,)), 0, hi),
            axis=0)
        live = (at + jnp.arange(rb, dtype=jnp.int32)) < n
        return lax.dynamic_update_slice(
            out, jnp.where(live[:, None], got, SENTINEL), (at, 0))

    return lax.fori_loop(0, blocks, block, out0), blocks


@jax.named_scope("jaxmc.merge.scatter")
def _rank_merge(seen, seen_count, keys, N, SC, K, multikey=False,
                n_prefix=None):
    """The O(new) seen-merge core SHARED by the single-chip resident
    level, the level engine and the mesh engine's shards (ISSUE 10;
    the _candidate_block_fn-style shared-plumbing pattern): the seen
    table keeps a sorted valid prefix [0:seen_count) as an INVARIANT,
    so a level only sorts its ≤N incoming keys, dedups them against
    the prefix with vectorized binary searches over the VALID keys
    alone, a block at a time and each key between its sampled
    neighbours' answers (_seen_probe: the sorted keys carry their
    valid rows first, ascending), and merges the genuinely-new keys in
    by rank.  No per-level re-sort of
    the seen table: the sort work is O(N log N), not
    O((SC+N) log (SC+N)).

    Rows move by GATHER through an inverse index built from one SCALAR
    scatter, never by a row scatter (ISSUE 25).  On the TPU v5e a row
    scatter cost 39-117 ns a row (the [SC,5] seen2 write 117, the
    [AccCap,4] compaction 39) against 4.3 ns a row for the jnp.take of
    _lower_bound and 9 ns an index for a scalar scatter — all read in
    one program's device trace (ledger, PR 24, cell desk-recheck-4p8)
    — and the row scatters were 53-77 % of the device's busy time in
    every benchmark cell.  On XLA:CPU, where this function was first
    shaped, the two cost about the same.

    The tail follows the rows that are live, as the probe does (ISSUE
    29): seen2 is the incoming table with its first
    _merge_blocks(seen_count2, SC) blocks of B = _merge_block_rows(SC)
    rows rebuilt, in place and from the last such block down (a
    backward memmove with insertions: a block reads seen rows at or
    below the rows it writes, and later turns read only below it), and
    the two index scatters push only the first _probe_blocks(n_live, N)
    blocks of sorted rows.  Every row past seen_count2 is left as it
    came in.

    A block takes its new rows from a window too, where the level has
    more key slots than _BUILD_WHOLE_KEYS (ISSUE 48; _build_form(N)):
    the new keys are compacted once into newk [N, K], the j-th new key
    at row j (_gather_prefix through nk_sidx, blocks of QB bounded on
    new_count), and the new rows of B consecutive output rows are
    consecutive rows of it, from the count of new rows below the block
    on — one dynamic_slice of B rows and a gather inside it, as for the
    seen rows.  At or under it a block gathers them from all N sorted
    keys, the program it was.

    seen [SC, K] (validity lane first, prefix sorted by the K-1 data
    words; every row from seen_count on INVALID, lane != 0), seen_count
    traced scalar, keys [N, K] unsorted candidate keys (invalid rows:
    lane 0 != 0, SENTINEL data — they sort last).

    Returns dict:
      new_count  how many sorted candidate keys are genuinely new
      nk_sidx    [N] each compacted new key's ORIGINAL row index in
                 `keys` (key-sorted order; ties keep first occurrence):
                 the new keys' in [0, new_count), 0 from there on.  The
                 engines fetch the new ROWS through that prefix — the
                 resident level in blocks bounded on new_count
                 (_gather_prefix, ISSUE 46)
      seen2      [SC, K] merged table — sorted valid prefix of length
                 seen_count + new_count, invalid tail (lane 1, SENTINEL
                 data in the blocks built; the incoming rows past
                 them).  Positions past SC are DROPPED: the
                 caller must treat seen_count2 > SC as an overflow and
                 roll the level back (seen_count2 still reports the
                 TRUE need, so growth can jump straight to it).
      seen_count2  seen_count + new_count (NOT cropped to SC).
      probe_blocks  query blocks the probe searched (× _probe_block_rows(N)
                 = the slots behind `search.slots_probed`).
      window_blocks  those of them that searched a window of the table
                 (ISSUE 45; × _probe_block_rows(N) = the slots behind
                 `search.slots_windowed`); None where SC is no more
                 than _probe_window_rows(SC) and the probe has none.
      merge_blocks  blocks of seen2 built (× _merge_block_rows(SC) = the
                 slots behind `search.slots_merged`).
      newkey_blocks  blocks of QB index slots the compaction of the new
                 keys ran (ISSUE 48; × _probe_block_rows(N) = the slots
                 behind `search.slots_keyed`); None where
                 _build_form(N) is "whole" and there is none.

      sort_slots  key slots the sort was given: the rung's (N without
                 n_prefix; the slots behind `search.slots_sorted`).

    multikey=True sorts the candidate keys with ONE stable multi-key
    lax.sort instead of the LSD chain (the level and mesh engines use
    it); the single-chip resident engine keeps the LSD chain its
    compile envelope was measured with.

    n_prefix (traced) promises what _seen_probe's n_live promises for
    queries: every VALID key sits in keys[0:n_prefix] (invalid rows may
    sit among them).  The sort then follows the rows that exist (ISSUE
    42): a lax.switch on _sort_rung_index runs the LSD chain over the
    smallest prefix of _sort_rungs(N) that holds them and leaves the
    rows past it where they are — invalid already, their sidx the
    arange.  The valid rows come out first and in the full sort's
    stable order; only the order of the invalid rows among themselves
    differs, and nothing below reads it.  Without it there is no
    ladder and no switch: the level and the mesh engines' keys are no
    prefix, and they keep the program they had."""
    sidx = jnp.arange(N, dtype=jnp.int32)
    rungs = (N,) if n_prefix is None or multikey else _sort_rungs(N)
    sort_slots = N
    with jax.named_scope("jaxmc.merge.sort"):
        if multikey:
            res = lax.sort(tuple(keys[:, j] for j in range(K)) + (sidx,),
                           num_keys=K, is_stable=True)
            kc = list(res[:K])
            sidx_s = res[K]
        elif len(rungs) == 1:
            kc, ec = _lsd_sort([keys[:, j] for j in range(K)], [sidx])
            sidx_s = ec[0]
        else:
            def sort_prefix(r):
                def branch(cols):
                    kc, ec = _lsd_sort([c[:r] for c in cols[:K]],
                                       [cols[K][:r]])
                    return tuple(
                        lax.dynamic_update_slice(c, s, (0,))
                        for c, s in zip(cols, kc + ec))
                return branch

            rung = _sort_rung_index(n_prefix, N)
            res = lax.switch(rung, [sort_prefix(r) for r in rungs],
                             tuple(keys[:, j] for j in range(K)) + (sidx,))
            kc = list(res[:K])
            sidx_s = res[K]
            sort_slots = jnp.asarray(rungs, jnp.int32)[rung]
    skeys = jnp.stack(kc, axis=1)
    # the table is read only once the keys are sorted: without the tie
    # XLA:TPU starts fetching the probe's copy of it into fast memory
    # (S(1)) while the sort still runs, and the sort's last passes lose
    # theirs (ISSUE 27: +12 % sort_device_s in the 4-process cell)
    skeys, seen = lax.optimization_barrier((skeys, seen))
    svalid = skeys[:, 0] == 0
    neq_prev = jnp.concatenate([
        jnp.array([True]),
        jnp.any(skeys[1:] != skeys[:-1], axis=1)])

    # the valid rows sorted first (lane 0 is the leading sort key), so
    # their count is the prefix the probe has to search; nothing below
    # reads found or lb of an invalid row (`new` masks them, pos_n is
    # used where `new`)
    n_live = jnp.sum(svalid, dtype=jnp.int32)
    found, lb, window_blocks = _probe_by_block(seen, seen_count, skeys, SC,
                                               n_live, sorted_keys=True)
    new = svalid & ~found & neq_prev
    new_count = jnp.sum(new, dtype=jnp.int32)
    seen_count2 = seen_count + new_count

    # the j-th new key (stable: key order kept) is the sorted row
    # whose cumsum rank is j.  Only the original indices are wanted
    # compacted, and a scalar scatter through the ranks does that.
    #
    # rank merge into seen2: pos(new j) = lb_j + j, strictly
    # increasing, and the seen rows keep their order in the positions
    # the new keys leave free — a bijection since new keys are
    # distinct from seen keys.  So one scalar scatter writes each new
    # key's sorted row number at its position (src, the inverse
    # index; -1 elsewhere) and one inclusive cumsum c over the marked
    # positions names every other row's source: seen row p - c below
    # seen_count, the invalid tail (lane 1, SENTINEL words) past it.
    # New keys whose position is >= SC park past the table and fall
    # off, as do the seen rows they would have pushed past it.
    #
    # Only a valid row can be new, and the valid rows sorted first: the
    # two scatters take the blocks of QB sorted rows the probe searched
    # and no other, QB indices a turn into the carried vectors (a
    # scalar scatter cost ~5 ns an index over all N slots, 76-93 % of
    # them padding).  Dropped rows get DISTINCT out-of-range indices
    # (N + sidx, P + sidx): unique_indices=True is a correctness
    # promise to XLA (advisor r2 rule).  The last block starts at
    # N - QB where QB does not divide N and writes its neighbour's rows
    # again: the same values.  P rounds SC up to whole blocks of B; the
    # engines' capacities are powers of two and P == SC.
    B = _merge_block_rows(SC)
    P = -(-SC // B) * B
    QB = _probe_block_rows(N)
    npos = jnp.cumsum(new.astype(jnp.int32)) - 1
    pos_n = lb + npos
    nk_tgt = jnp.where(new, npos, N + sidx)
    src_tgt = jnp.where(new & (pos_n < SC), pos_n, P + sidx)

    def index_block(b, out):
        nk_sidx, src = out
        at = jnp.minimum(b * QB, N - QB)
        rows = at + jnp.arange(QB, dtype=jnp.int32)
        nk_sidx = nk_sidx.at[lax.dynamic_slice(nk_tgt, (at,), (QB,))] \
            .set(lax.dynamic_slice(sidx_s, (at,), (QB,)), mode="drop",
                 unique_indices=True)
        src = src.at[lax.dynamic_slice(src_tgt, (at,), (QB,))] \
            .set(rows, mode="drop", unique_indices=True)
        return nk_sidx, src

    # constants of the operands' TYPE (n_live - n_live), as
    # _seen_probe's lb0: the carries leave the loops device-varying
    # under shard_map
    zero = n_live - n_live
    nk_sidx, src = lax.fori_loop(
        0, _probe_blocks(n_live, N), index_block,
        (jnp.zeros((N,), jnp.int32) + zero,
         jnp.full((P,), -1, jnp.int32) + zero))
    c = jnp.cumsum((src >= 0).astype(jnp.int32))

    # The new rows of a block (ISSUE 48): the row at position p that is
    # new is the (c[p] - 1)-th new key — a new key parked past SC has
    # every new key in the table before it, so c ranks as npos does —
    # and a block holds at most B of them, consecutive from the
    # c[p0 - 1]-th.  With the new keys compacted in rank order (ties
    # keep the first occurrence and equal keys are equal words, so
    # keys[nk_sidx[j]] IS the j-th new sorted key) they are a window of
    # WN rows that always fits: no branch.
    windowed = _build_form(N) == "window"
    newkey_blocks = None
    if windowed:
        WN = min(B, N)
        newk, newkey_blocks = _gather_prefix(keys, nk_sidx, new_count, N,
                                             QB, zero=zero)

    tail = jnp.concatenate([jnp.ones((1, 1), jnp.int32),
                            jnp.full((1, K - 1), SENTINEL, jnp.int32)],
                           axis=1)
    merge_blocks = _merge_blocks(seen_count2, SC)

    # The seen rows of B consecutive output rows lie within B rows of
    # the table, so a block gathers from its own window of it
    # (_MERGE_BLOCK_ROWS says why) — the window of the table AS CARRIED:
    # block p0 needs seen rows [p0 - c(p0), p0 + B), the turns before
    # it wrote [p0 + B, ...) and the turns after it read below p0 + B
    # only, so the build needs no second table.
    def block(i, table):
        p0 = (merge_blocks - 1 - i) * B
        src_b = lax.dynamic_slice(src, (p0,), (B,))
        is_new = src_b >= 0
        rows_b = p0 + jnp.arange(B, dtype=jnp.int32)
        c_b = lax.dynamic_slice(c, (p0,), (B,))
        src_s = rows_b - c_b
        # the first seen row the block can need: in [0, p0]
        at = src_s[0] + is_new[0]
        window = lax.dynamic_slice(table, (at, 0), (B, K))
        from_seen = jnp.take(window, jnp.clip(src_s - at, 0, B - 1),
                             axis=0)
        if windowed:
            # the new keys below the block: c[p0 - 1], clamped so that
            # the slice stays inside newk
            j0 = jnp.minimum(c_b[0] - is_new[0], N - WN)
            nwin = lax.dynamic_slice(newk, (j0, 0), (WN, K))
            from_new = jnp.take(nwin, jnp.clip(c_b - 1 - j0, 0, WN - 1),
                                axis=0)
        else:
            from_new = jnp.take(skeys, jnp.clip(src_b, 0, N - 1), axis=0)
        is_seen = (src_s < seen_count)[:, None]
        rows = jnp.where(is_new[:, None], from_new,
                         jnp.where(is_seen, from_seen, tail))
        return lax.dynamic_update_slice(table, rows, (p0, 0))

    if P != SC:
        seen = jnp.concatenate([seen, jnp.broadcast_to(tail, (P - SC, K))])
    # In the window form BOTH of a block's gathers read row-major copies
    # of B-row slices, the select of their results is row-major, and
    # XLA:TPU's layout assignment then carries the whole table so —
    # s32[SC,5]{1,0:T(8,128)}, a row padded to 128 lanes: 8 GB for
    # desk-deep-4p's 336 MB table, and the program does not fit the chip
    # (compiled for a described v5e, PERF.md §6, PR 48).  Held to the
    # layout it arrives in on both sides of the loop, the table stays
    # {0,1} and a block pays one [B, K] relayout, as it did.
    if windowed:
        seen = with_layout_constraint(seen, _WORDS_MAJOR)
    seen2 = lax.fori_loop(0, merge_blocks, block, seen)[:SC]
    if windowed:
        seen2 = with_layout_constraint(seen2, _WORDS_MAJOR)
    return dict(new_count=new_count, nk_sidx=nk_sidx, seen2=seen2,
                seen_count2=seen_count2,
                probe_blocks=_probe_blocks(n_live, N),
                window_blocks=window_blocks,
                merge_blocks=merge_blocks, newkey_blocks=newkey_blocks,
                sort_slots=sort_slots)


@jax.named_scope("jaxmc.canon")
def _canon(canon_fn, rows):
    """The cfg SYMMETRY canonicaliser (compile/symmetry2.py) over a block
    of unpacked rows, under a device scope of its own inside
    `jaxmc.keys`."""
    return canon_fn(rows)


@jax.named_scope("jaxmc.keys")
def _keys_of_rows(plan, view_fn, canon_fn, fp_mode, rows, valid):
    """(keys, packed_rows, pack_ovf) for a block of UNPACKED rows.

    keys: [N, K] dedup key lanes — an explicit validity lane FIRST
    (0=valid, 1=invalid, sorting after all valid rows; SENTINEL
    data), then the key basis: the cfg VIEW's value lanes when one
    is declared, else the BIT-PACKED row (compile/pack.py) —
    fingerprinted to 4 words in fp mode.

    packed_rows: [N, PW] the packed rows for engine storage
    (SENTINEL-filled where invalid).

    pack_ovf: scalar bool — some VALID row had a guarded lane
    outside its profiled bit range; the engines route it into the
    overflow channel as kernel2.OV_PACK (an exact abort naming
    JAXMC_PACK=0, never a silently wrong count).

    With cfg SYMMETRY, the KEY basis is the orbit's canonical
    representative (compile/symmetry2.py) while the stored packed
    row keeps the original state — same partition, same traces, as
    the unpacked engines."""
    packed, povf = plan.pack_rows(rows)
    pack_ovf = jnp.any(povf & valid)
    packed = jnp.where(valid[:, None], packed, SENTINEL)
    if view_fn is not None:
        # SYMMETRY composes with VIEW exactly like the interp's
        # state_fingerprint: the view evaluates over the orbit's
        # CANONICAL representative (view of the raw row would count
        # symmetric states as distinct — caught in review by a
        # 2-process SYMMETRY+VIEW repro, 17/9 vs the interp's 12/6)
        vrows = rows
        if canon_fn is not None:
            vrows = jnp.where(valid[:, None], _canon(canon_fn, rows),
                              rows)
        kb = jax.vmap(view_fn)(vrows)
        if kb.ndim == 1:
            kb = kb[:, None]
    elif canon_fn is not None:
        crows = jnp.where(valid[:, None], _canon(canon_fn, rows), rows)
        kb, cpovf = plan.pack_rows(crows)
        kb = jnp.where(valid[:, None], kb, SENTINEL)
        pack_ovf = pack_ovf | jnp.any(cpovf & valid)
    else:
        kb = packed
    k = fingerprint128(kb) if fp_mode else kb
    k = jnp.where(valid[:, None], k, SENTINEL)
    vlane = jnp.where(valid, 0, 1).astype(jnp.int32)
    return (jnp.concatenate([vlane[:, None], k], axis=1), packed,
            pack_ovf)


def _has_executable(fn) -> bool:
    """Does jax hold an executable for this (prof-wrapped) jitted
    function already?"""
    size = getattr(getattr(fn, "__wrapped__", fn), "_cache_size", None)
    return bool(size and size())


class _LiveGraph:
    """Host-side behavior-graph accumulator for device runs.

    Mirrors the interp engine's bookkeeping (engine/explore.py): kept
    states get dense ids in discovery order; edges record every
    (parent, kept-successor) step including re-visits of already-seen
    states; parents/labels form the BFS tree for trace reconstruction.
    Constraint-discarded successors never enter the graph — the same
    mask that keeps them off the device frontier keeps them out here."""

    def __init__(self, labels_flat: List[str], collect_edges: bool):
        self.labels_flat = labels_flat
        self.collect_edges = collect_edges
        self.rows: List[np.ndarray] = []
        self.sid_by_key: Dict[bytes, int] = {}
        self.parents: List[Optional[int]] = []
        self.labels: List[str] = []
        self.edges: List[Tuple[int, int]] = []

    def add_inits(self, init_rows, explored_idx) -> np.ndarray:
        sids = []
        for i in explored_idx:
            row = np.array(init_rows[i], copy=True)
            sid = len(self.rows)
            self.rows.append(row)
            self.sid_by_key[row.tobytes()] = sid
            self.parents.append(None)
            self.labels.append("Initial predicate")
            sids.append(sid)
        return np.asarray(sids, dtype=np.int64)

    def add_level(self, new_rows, new_prov, par_div: int,
                  frontier_sids: np.ndarray) -> np.ndarray:
        """Register this level's kept rows; prov = action*par_div + f."""
        sids = []
        for i in range(len(new_rows)):
            row = np.array(new_rows[i], copy=True)
            sid = len(self.rows)
            self.rows.append(row)
            self.sid_by_key[row.tobytes()] = sid
            p = int(new_prov[i])
            a, f = p // par_div, p % par_div
            self.parents.append(int(frontier_sids[f]))
            self.labels.append(self.labels_flat[a])
            sids.append(sid)
        return np.asarray(sids, dtype=np.int64)

    def add_edges(self, rows: np.ndarray, parent_f: np.ndarray,
                  frontier_sids: np.ndarray) -> None:
        """Record edges (frontier_sids[parent_f[i]] -> sid of rows[i]) for
        pre-masked kept candidates; call after add_level so same-level
        successors resolve."""
        if not self.collect_edges:
            return
        for i in range(len(rows)):
            t = self.sid_by_key.get(rows[i].tobytes())
            if t is None:
                continue  # fp-collision shadow; counts already report it
            self.edges.append(
                (int(frontier_sids[int(parent_f[i])]), t))


class TpuExplorer:
    def __init__(self, model: Model, log: Callable[[str], None] = None,
                 max_states: Optional[int] = None, store_trace: bool = True,
                 progress_every: float = 30.0,
                 bounds: Optional[Bounds] = None,
                 sample_cfg: Tuple[int, int, int] = (800, 40, 60),
                 host_seen: bool = False, chunk: int = 2048,
                 resident: bool = False,
                 checkpoint_path: Optional[str] = None,
                 checkpoint_every: float = 600.0,
                 resume_from: Optional[str] = None,
                 extra_samples: Optional[List[Dict[str, Any]]] = None,
                 relayouts_left: int = 3,
                 pin_interp_arms: bool = False,
                 res_caps: Optional[Dict[str, int]] = None,
                 cap_profile: bool = True,
                 final_checkpoint: bool = False,
                 backend: Optional["BackendDescriptor"] = None,
                 seen_mode: str = "auto",
                 seen_cap: Optional[int] = None,
                 spill_dir: Optional[str] = None,
                 host_tier_keys: Optional[int] = None,
                 lift_consts: Optional[Tuple[str, ...]] = None,
                 por: bool = False,
                 donor: Optional["TpuExplorer"] = None,
                 inits: Optional[List[Dict[str, Any]]] = None):
        # cross-model batching (ISSUE 13): `lift_consts` compiles the
        # named CONSTANTs as traced kernel inputs instead of baked
        # scalars, so one compiled program serves every model that
        # differs only in those values; `donor` clones a FOLLOWER
        # engine that reuses the donor's layout + compiled kernels
        # (zero kernel builds) while keeping its own model, init
        # states, seen store and checkpoint surface.
        self._hstep_override: Optional[Callable] = None
        # device POR (ISSUE 18): the persistent-set filter runs INSIDE
        # the fused step (level/resident/host_seen), reusing the seen
        # probe the merge performs anyway — the plan (instance->arm map
        # + por-safe mask) is resolved lazily by _por_plan(), which
        # names the refusal when the reduction cannot run
        self.por = bool(por)
        self.por_reason: Optional[str] = None
        self._por_memo: Any = _POR_UNSET
        self._program_sig_memo: Any = _SIG_UNSET
        self._por_stats = {"ample": 0, "expanded": 0, "masked": 0}
        if donor is not None:
            self._clone_from_donor(
                donor, model, log=log, max_states=max_states,
                store_trace=store_trace, progress_every=progress_every,
                checkpoint_path=checkpoint_path,
                checkpoint_every=checkpoint_every,
                resume_from=resume_from,
                final_checkpoint=final_checkpoint, inits=inits)
            return
        self._lift_names: Tuple[str, ...] = tuple(lift_consts or ())
        if self._lift_names and not host_seen:
            raise ModeError(
                "lifted-constant (batchable) engines run in host_seen "
                "mode only — the level/resident/mesh steps do not "
                "thread constant lanes")
        self.model = model
        # the device layer this engine is compiled FOR (ISSUE 11): one
        # descriptor instead of per-engine re-derivation from global
        # jax state — platform, donation policy and the capacity-
        # profile namespace all read from it, so caps learned on one
        # platform can never warm-start another
        from . import describe_backend
        self.backend_desc = backend if backend is not None \
            else describe_backend()
        # persist a checkpoint when the search COMPLETES (not just on
        # truncation): the serve daemon's warm-resume source — an
        # identical later job resumes it, replays the stored totals
        # over an empty frontier, and finishes in one dispatch
        self.final_checkpoint = final_checkpoint
        # same funnel as cli.py: silent on stdout by default, but the
        # strings still mirror into the telemetry trace
        self.log = log if log is not None else obs.Logger(quiet=True)
        self.max_states = max_states
        self.store_trace = store_trace
        self.progress_every = progress_every
        self.bounds = bounds or Bounds()
        self.host_seen = host_seen
        self.chunk = chunk
        self.resident = resident
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = checkpoint_every
        self.resume_from = resume_from
        self.sample_cfg = sample_cfg
        # ADAPTIVE RELAYOUT (hybrid, host_seen): when a compile-recovery
        # demotion fires because a value SHAPE was never observed by the
        # layout sampler (a deep model's rare message variant), the
        # engine re-samples from the abort-time frontier, rebuilds the
        # layout and kernels with the enriched observation set, and
        # restarts COMPILED — falling back to whole-arm interpretation
        # only after relayouts_left attempts.
        self.extra_samples = list(extra_samples or [])
        self.relayouts_left = relayouts_left
        # expansion-mode pin (ISSUE 5): the corpus manifest knows this
        # model's arms ALL demote to the interpreter — skip grounding +
        # kernel construction + forced tracing entirely instead of
        # paying minutes of futile XLA work (MCInnerSerial burned 213s
        # building 13 kernels it then demoted, in the r05 jax sweep).
        self.pin_interp_arms = pin_interp_arms
        self._res_caps_hint = dict(res_caps) if res_caps else None
        self.cap_profile = cap_profile
        self._last_frontier_np: Optional[np.ndarray] = None

        tel = obs.current()
        # Init is walked ONCE and the list handed on (ISSUE 52): to the
        # sampler, whose samples begin with it, and through the layout
        # build, which encodes every sample, to the first search
        self._init_walks0 = _init_walks(tel)
        self.init_states = self._enumerate_init(inits)
        bfs_n, walks, depth = sample_cfg
        with tel.span("layout_sample", bfs_states=bfs_n, walks=walks,
                      walk_depth=depth):
            sampled = sample_states(model, bfs_states=bfs_n,
                                    n_walks=walks, walk_depth=depth,
                                    inits=self.init_states)
        sampled = list(sampled) + self.extra_samples
        # static bounds inference (ISSUE 9): a converged interval proof
        # turns observed-range guarded int lanes into proven-width lanes
        # — the OV_PACK re-sample cycle cannot fire on a proven lane.
        # The fixpoint result is cached on the model so relayout
        # restarts and mesh subclasses do not re-run it.
        self._static_bounds = None
        from .. import analyze as _analyze
        if _analyze.bounds_enabled():
            rep = getattr(model, "_bounds_report", _SENTINEL_NO_REPORT)
            if rep is _SENTINEL_NO_REPORT:
                with tel.span("analyze_bounds"):
                    rep = _analyze.infer_state_bounds(model)
                try:
                    model._bounds_report = rep
                except AttributeError:
                    pass
            if rep is not None:
                # per-element structured bounds when the report carries
                # them (ISSUE 15: container element lanes pack at their
                # own proven widths); merged/shim reports (batch donor
                # builds) still provide whole-variable intervals
                ebf = getattr(rep, "element_bounds", None)
                self._static_bounds = ebf() if callable(ebf) \
                    else rep.lane_bounds()
                tel.gauge("analyze.bounds_converged",
                          bool(rep.converged))
        with tel.span("layout_build", samples=len(sampled)):
            self.layout, rows = build_layout2(
                model, sampled, self.bounds,
                static_bounds=self._static_bounds)
        # the initial states' rows, for `_prepare_init` (a copy: the
        # samples' matrix goes); None where the layout could not encode
        # one of them, and the first search raises what encoding it does
        n = len(self.init_states)
        self._init_rows_built: Optional[np.ndarray] = \
            rows[:n].copy() if len(rows) >= n else None
        del sampled, rows
        self.kc = KernelCtx(model, self.layout, self.bounds)
        # per-model lifted-constant values, in _lift_names order: the
        # runtime input vector the shared kernels read instead of baked
        # scalars (empty for ordinary engines — same code path)
        self._cvec = np.asarray([int(model.defs[n])
                                 for n in self._lift_names], np.int32)
        # dynamic \E expansion applies to message tables AND to
        # state-dependent intervals (\E i \in 1..Len(q), AlternatingBit's
        # Lose); slots beyond the actual element count are mask-disabled.
        #
        # Hybrid execution (VERDICT r3 #2): Next splits into disjunct
        # arms; an arm whose grounding or kernel compilation fails is
        # demoted to exact interpreter enumeration over decoded frontier
        # states (host_seen mode only) instead of rejecting the spec.
        # Kernel CompileErrors surface lazily at jit-trace time, so each
        # compiled unit is force-traced here with jax.eval_shape
        # (abstract evaluation — no XLA compile cost).
        row_spec = jax.ShapeDtypeStruct((self.layout.width,), jnp.int32)
        slot_spec = jax.ShapeDtypeStruct((), jnp.int32)
        self.arms = split_arms(model)
        self.actions = []
        self.compiled = []
        self._ca_arm: List[int] = []  # arm index per compiled action
        self.fb_arms: List[Tuple[Any, str]] = []  # (ActionArm, reason)
        # per-arm compile introspection (ISSUE 2): jaxpr equation count
        # and HLO flops/bytes per kernel, aggregated per arm label. The
        # introspection trace replaces the eval_shape forced trace, so
        # the only extra cost vs an untelemetered build is the lowering
        # for cost_analysis (JAXMC_COMPILE_INTROSPECT=0 skips it).
        arm_costs: Dict[str, Dict[str, int]] = {}
        zero_row = jnp.zeros((self.layout.width,), jnp.int32)
        zero_slot = jnp.zeros((), jnp.int32)
        # transient per-arm compile failures (a runtime hiccup mid-
        # lowering, injected compile_fail faults) get a bounded retry
        # with backoff before the failure escapes to cli.py's demotion
        # path; REAL CompileErrors are deterministic and still demote
        # the arm to the interpreter immediately, as before
        compile_retries = int(os.environ.get("JAXMC_COMPILE_RETRIES",
                                             "2"))
        from .. import faults as _faults
        if self.pin_interp_arms:
            self.fb_arms = [(arm, "pinned interp-arms (corpus "
                                  "manifest): kernel construction "
                                  "skipped") for arm in self.arms]
        # statically-predicted demotions (ISSUE 9): arms the analyzer is
        # CERTAIN compile_action2 would demote skip grounding + kernel
        # construction + forced tracing outright — the derived
        # generalization of the manifest's measured pin_interp_arms
        # pins.  The verdict string IS the build-time reason string
        # (kernel2's shared message constants), so the demotion table,
        # the ModeError text and the sweep notes read identically on
        # either path.
        self.arm_verdicts: Dict[int, str] = {}
        if not self.pin_interp_arms and _analyze.predict_enabled() \
                and self.arms:
            with tel.span("analyze_arms", arms=len(self.arms)):
                self.arm_verdicts = _analyze.predict_arm_demotions(
                    model, self.arms)
            if self.arm_verdicts:
                tel.counter("analyze.predicted_demotions",
                            len(self.arm_verdicts))
                tel.gauge("analyze.arm_verdicts",
                          {(self.arms[i].label or "Next"): r
                           for i, r in sorted(self.arm_verdicts.items())})
        for ai, arm in enumerate(
                () if self.pin_interp_arms else self.arms):
            if ai in self.arm_verdicts:
                # zero futile build attempts: the arm goes straight to
                # the interpreter with the predicted (== build-time)
                # reason
                self.fb_arms.append((arm, self.arm_verdicts[ai]))
                continue
            try:
                for attempt in range(compile_retries + 1):
                    # per-ATTEMPT introspection buffer: the rollup
                    # (arm_costs + the *_total counters) commits only
                    # when the attempt succeeds, so a retried arm never
                    # double-counts the kernels introspected before the
                    # transient failure (the per-attempt span still
                    # carries its own attrs — that is honest span data)
                    att_costs: Dict[str, int] = {}
                    try:
                        # the span covers grounding + kernel build + the
                        # forced abstract trace — the per-arm compile
                        # cost the bench forensics need (BENCH_r05:
                        # nothing said whether compile or BFS ate the
                        # deadline)
                        with tel.span("compile_arm",
                                      arm=arm.label or "Next") as asp:
                            _faults.inject("compile_fail",
                                           arm=arm.label or "Next")
                            gas = ground_arm(model, arm,
                                             dyn_slots=self.bounds.kv_cap)
                            cas = []
                            for ga in gas:
                                ca = compile_action2(self.kc, ga)
                                if self._lift_names:
                                    # lifted build: the forced abstract
                                    # trace installs const TRACERS so
                                    # compile success/demotion is
                                    # decided exactly as the shared
                                    # run-time trace will decide it
                                    # (introspection skipped — it would
                                    # re-trace without the lanes)
                                    cspec = jax.ShapeDtypeStruct(
                                        (len(self._lift_names),),
                                        jnp.int32)
                                    if ca.n_slots:
                                        jax.eval_shape(
                                            partial(self._traced_with,
                                                    ca.fn),
                                            cspec, row_spec, slot_spec)
                                    else:
                                        jax.eval_shape(
                                            partial(self._traced_with,
                                                    ca.fn),
                                            cspec, row_spec)
                                    cas.append(ca)
                                    continue
                                if tel.enabled:
                                    # the introspection trace IS the
                                    # forced abstract trace (same lazy
                                    # CompileError/RecursionError
                                    # surface as eval_shape) — one
                                    # trace per kernel either way
                                    info = introspect_kernel(
                                        ca.fn, (zero_row, zero_slot)
                                        if ca.n_slots else (zero_row,))
                                    for k, v in info.items():
                                        att_costs[k] = \
                                            att_costs.get(k, 0) + v
                                        asp.attrs[k] = \
                                            asp.attrs.get(k, 0) + v
                                elif ca.n_slots:
                                    jax.eval_shape(ca.fn, row_spec,
                                                   slot_spec)
                                else:
                                    jax.eval_shape(ca.fn, row_spec)
                                cas.append(ca)
                        if att_costs:
                            acc = arm_costs.setdefault(
                                arm.label or "Next", {})
                            for k, v in att_costs.items():
                                acc[k] = acc.get(k, 0) + v
                                tel.counter(
                                    {"jaxpr_eqns":
                                     "compile.jaxpr_eqns_total",
                                     "hlo_flops":
                                     "compile.hlo_flops_total",
                                     "hlo_bytes":
                                     "compile.hlo_bytes_total"}[k], v)
                        break
                    except RecursionError:
                        raise  # deterministic (RuntimeError subclass)
                    except (_faults.FaultInjected, OSError,
                            RuntimeError) as ex:
                        if attempt >= compile_retries:
                            raise
                        tel.counter("compile.retries")
                        self.log(f"-- compile_arm "
                                 f"{arm.label or 'Next'}: transient "
                                 f"failure ({ex}); retrying "
                                 f"({attempt + 1}/{compile_retries})")
                        time.sleep(min(0.1 * (2 ** attempt), 2.0))
            except CompileError as e:
                self.fb_arms.append((arm, str(e)))
                continue
            except RecursionError:
                # a RECURSIVE operator with symbolic arguments unrolls
                # forever at trace time — demote the arm like any other
                # uncompilable construct instead of crashing the build
                self.fb_arms.append(
                    (arm, "recursive operator expansion diverges at "
                          "compile time (RecursionError)"))
                continue
            self.actions.extend(gas)
            self.compiled.extend(cas)
            self._ca_arm.extend([ai] * len(cas))
        if arm_costs:
            # machine-readable per-arm compile-cost map (schema v2):
            # {arm label -> {jaxpr_eqns, hlo_flops?, hlo_bytes?}}
            tel.gauge("compile.arm_cost", arm_costs)
        # per-arm demotion reasons (ISSUE 5 / VERDICT r5 #4): the sweep
        # log used to say only "13 arms interp-demoted" — name each arm
        # and WHY, so a mechanical arm wrongly demoted (vs a genuinely
        # recursive one) is visible instead of folded into a count
        for _arm, _reason in self.fb_arms:
            self.log(f"-- arm {_arm.label or 'Next'}: interp-demoted "
                     f"({_reason})")
        # kernels that compiled only by DEMOTING a guard conjunct (False
        # + abort flag) under-approximate behind a runtime abort. Most
        # demotions never fire (raft's Receive reads fields of message
        # variants that never occur under the micro constraints); when
        # one DOES fire, the host_seen engine demotes those arms to the
        # interpreter and restarts the search (see run()) instead of
        # reporting a spurious capacity overflow.
        self._demotable = sorted({self._ca_arm[i]
                                  for i, ca in enumerate(self.compiled)
                                  if ca.demoted_guards})
        # flat instance list: slotted kernels contribute n_slots rows
        self.labels_flat = []
        for ca in self.compiled:
            if ca.n_slots:
                self.labels_flat.extend(
                    [ca.label] * ca.n_slots)
            else:
                self.labels_flat.append(ca.label)
        # cfg SYMMETRY: canonicalize rows to their orbit representative
        # before fingerprinting (same partition, hence same counts, as
        # the interp's make_canonicalizer); encodings the transform
        # builder rejects fall back to the unreduced search with the
        # SYMMETRY warning
        self.canon_fn = None
        self._sym_fallback: Optional[str] = None
        if model.symmetry is not None:
            from ..compile.symmetry2 import build_canon2
            try:
                self.canon_fn = build_canon2(model, self.layout)
            except CompileError as e:
                self._sym_fallback = str(e)
        # identity-group disclosure (ISSUE 5 satellite): build_canon2
        # returns None BY DESIGN when every declared permutation is the
        # identity — no reduction exists to diverge from, so no
        # UNREDUCED-FALLBACK warning belongs here (the interp's
        # make_canonicalizer returns None for the same group, so counts
        # match TLC exactly). Only a genuine CompileError fallback
        # (self._sym_fallback) reports divergence.
        self.sym_identity = (model.symmetry is not None
                             and self.canon_fn is None
                             and self._sym_fallback is None)
        # which canonicaliser runs on the device: "sorted", "unrolled"
        # (symmetry2.build_canon2) or "none" (identity group, fallback,
        # no SYMMETRY); disclosed where a cfg declares SYMMETRY
        self.sym_form = getattr(self.canon_fn, "form", "none")
        if model.symmetry is not None:
            tel.gauge("symmetry.form", self.sym_form)
            tel.gauge("symmetry.group_order",
                      getattr(self.canon_fn, "group_order", 1))
        # predicates likewise force-traced; uncompilable ones demote to
        # host-side interpreter evaluation over decoded rows (hybrid).
        # A TRACE-TIME BUDGET (JAXMC_PRED_TRACE_BUDGET seconds, default
        # 15) also demotes predicates whose symbolic programs explode —
        # MCVoting's inductive Inv unrolls its quantifier towers into a
        # ~50k-op jaxpr whose XLA:CPU compile alone blew the r3 sweep's
        # 900 s case timeout; the exact interpreter checks such
        # predicates on new rows at negligible cost instead.
        budget = float(os.environ.get("JAXMC_PRED_TRACE_BUDGET", "15"))

        def _compile_preds(pairs, may_demote_on_budget):
            """(compiled, demoted) for a predicate list. Uncompilable
            predicates always demote (hybrid checks them exactly); a
            predicate whose abstract trace exceeds the budget demotes
            only when may_demote_on_budget — callers keep slow compiled
            predicates when demotion would make the run unsupported
            (non-host_seen modes; constraints under temporal/refinement
            PROPERTYs), so a loaded box never REFUSES a spec an idle
            box accepts."""
            compiled, demoted = [], []
            for nm, ex in pairs:
                f = compile_predicate2(self.kc, ex)
                t_tr = time.time()
                try:
                    if self._lift_names:
                        jax.eval_shape(
                            partial(self._traced_with, f),
                            jax.ShapeDtypeStruct(
                                (len(self._lift_names),), jnp.int32),
                            row_spec)
                    else:
                        jax.eval_shape(f, row_spec)
                except CompileError as e:
                    demoted.append((nm, ex, str(e)))
                    continue
                except RecursionError:
                    demoted.append(
                        (nm, ex, "recursive operator expansion diverges "
                                 "at compile time (RecursionError)"))
                    continue
                t_tr = time.time() - t_tr
                if t_tr > budget and may_demote_on_budget:
                    demoted.append(
                        (nm, ex,
                         f"trace budget exceeded ({t_tr:.0f}s > "
                         f"{budget:.0f}s [JAXMC_PRED_TRACE_BUDGET]; the "
                         f"compiled program would dwarf the model)"))
                    continue
                compiled.append((nm, f))
            return compiled, demoted

        with tel.span("compile_predicates",
                      invariants=len(model.invariants),
                      constraints=len(model.constraints)):
            self.inv_fns, self.fb_invs = _compile_preds(
                model.invariants, host_seen)
            self.constraint_fns, self.fb_cons = _compile_preds(
                model.constraints, host_seen and not model.properties)
        if model.action_constraints:
            raise CompileError("action constraints not compiled yet - "
                               "use the interp backend")
        # cfg VIEW (ISSUE 6): compile V to its value lanes and key the
        # dedup on them — TLC fingerprints the view, not the state
        # (ConfigFileGrammar.tla:8-11); the kept rows stay full states
        # so traces/decodes are unchanged.  An uncompilable view still
        # refuses the spec (the interp backend remains its checker).
        self.view_fn = None
        self.view_width = 0
        if getattr(model, "view", None) is not None:
            try:
                self.view_fn = compile_value2(self.kc, model.view)
                vsh = jax.eval_shape(self.view_fn, row_spec)
                self.view_width = int(np.prod(vsh.shape)) \
                    if vsh.shape else 1
            except RecursionError:
                raise CompileError(
                    "cfg VIEW expression recurses unboundedly at compile "
                    "time - use --backend interp")
            if self.view_width == 0:
                raise CompileError(
                    "cfg VIEW evaluates to zero lanes - use --backend "
                    "interp")
        # refinement PROPERTYs check stepwise on the host over the
        # streamed candidate edges — same verdicts as the interp backend
        from ..engine.refinement import build_refinement_checkers
        self.refiners, self.unrefined = build_refinement_checkers(model)
        self._ref_pair_cache: set = set()
        # temporal (liveness) obligations check over the behavior graph
        # after the search completes, exactly like the interp backend:
        # kept states/edges stream to the host during the run and feed
        # engine/liveness.py — same classifier, same checker, same verdict
        from ..engine.liveness import collect_obligations
        self.live_obligations, self.live_unsupported, self.collect_edges = \
            collect_obligations(model, self.refiners)
        self.hybrid = bool(self.fb_arms or self.fb_invs or self.fb_cons)
        if self.hybrid:
            reasons = "; ".join(
                [f"action arm {a.label or 'Next'}: {r}"
                   for a, r in self.fb_arms]
                + [f"invariant {nm}: {r}" for nm, _, r in self.fb_invs]
                + [f"constraint {nm}: {r}" for nm, _, r in self.fb_cons])
            if not host_seen:
                raise ModeError(
                    "spec needs hybrid execution (uncompilable units "
                    "demoted to the exact interpreter), which only the "
                    "host_seen device mode runs — pass host_seen=True; "
                    f"demoted units: {reasons}")
            if self.fb_cons and (self.collect_edges or self.refiners):
                raise CompileError(
                    "uncompilable CONSTRAINT together with temporal/"
                    "refinement PROPERTYs is not supported on the device "
                    f"backend — use --backend interp; units: {reasons}")
            if not self.compiled and self.fb_arms:
                self.log("hybrid: EVERY action arm fell back to the "
                         "interpreter — the device does hashing/dedup "
                         "only on this model")
        # device flat-instance count; fallback arm j takes provenance
        # index A + j so traces and the behavior graph resolve labels
        # through one table
        self.A = len(self.labels_flat)
        self.labels_flat = self.labels_flat + \
            [arm.label or "Next" for arm, _ in self.fb_arms]
        self.W = self.layout.width
        # ENGINE storage format (ISSUE 6): rows cross the kernel/engine
        # boundary BIT-PACKED (compile/pack.py) — the frontier, the seen
        # table, trace levels, checkpoints and the candidate streams all
        # hold [*, PW] packed rows; kernels unpack to [*, W] lanes at
        # the top of each jitted step.  The exact-dedup/fp128 threshold
        # is recomputed over the PACKED width (or the view width when
        # cfg VIEW keys the dedup).
        self.PW = self.layout.packed_width
        self.plan = self.layout.plan
        self.key_width = self.view_width if self.view_fn is not None \
            else self.PW
        self.fp_mode = self.key_width > FP_THRESHOLD
        # expansion-mode disclosure, machine-readable (mirrors the sweep's
        # per-case note): gauges overwrite on relayout restarts so the
        # artifact reports the engine that actually ran
        tel.gauge("expand.arms_total", len(self.arms))
        tel.gauge("expand.arms_compiled",
                  len(self.arms) - len(self.fb_arms))
        tel.gauge("expand.arms_interp", len(self.fb_arms))
        tel.gauge("expand.compiled_instances", self.A)
        tel.gauge("expand.invariants_interp", len(self.fb_invs))
        tel.gauge("expand.constraints_interp", len(self.fb_cons))
        if model.constraints:
            # ... and how many the device judges itself (ISSUE 51)
            tel.gauge("constraint.compiled", len(self.constraint_fns))
        tel.gauge("expand.mode",
                  "compiled" if not self.fb_arms
                  else ("hybrid" if self.A else "interp-arms"))
        tel.gauge("layout.width_lanes", self.W)
        tel.gauge("layout.packed_width_lanes", self.PW)
        # dedup key lanes: an explicit validity lane FIRST (0=valid row,
        # 1=invalid) — validity must never be encoded in-band in hash
        # output or state lanes, either could legitimately equal SENTINEL
        self.K = (4 if self.fp_mode else self.key_width) + 1
        tel.gauge("dedup.mode",
                  ("fp128" if self.fp_mode else "exact")
                  + ("-view" if self.view_fn is not None
                     else ("-packed" if not self.plan.identity else "")))
        # buffer donation (ISSUE 6): donate the seen table and frontier
        # into the jitted steps so XLA updates them in place instead of
        # allocating a copy per level.  XLA:CPU ignores donation (with a
        # warning), so it defaults on only for accelerator backends;
        # JAXMC_DONATE=1/0 forces it either way — the policy lives on
        # the backend descriptor since ISSUE 11.
        self.donate = bool(self.backend_desc.donate)
        tel.gauge("device.donation", bool(self.donate))
        tel.gauge("backend.platform", self.backend_desc.platform)
        tel.gauge("backend.profile_ns", self.backend_desc.profile_ns)
        self._trace_lock = threading.Lock()
        self._step_cache: Dict[Tuple[int, int], Callable] = {}
        self._hstep_cache: Dict[int, Callable] = {}
        self._hstep_group_jits: Dict[
            int, Tuple[List[Callable], List[np.ndarray]]] = {}
        self._newcheck_cache: Dict[int, Callable] = {}
        self._res_cache: Dict[Tuple[int, ...], Callable] = {}
        self._walk_cache: Dict[Tuple[int, int, int], Callable] = {}
        self._hostkeys_cache: Dict[int, Callable] = {}
        self._pkeys_cache: Dict[int, Callable] = {}
        # capacities learned by previous resident runs on this instance:
        # a warm-up run trains them so the timed run never overflows
        # (and therefore never recompiles)
        self._res_caps: Optional[Dict[str, int]] = None
        self._res_maxlvl = 64  # levels per resident dispatch
        if resident:
            if host_seen:
                raise ModeError(
                    "resident and host_seen are mutually exclusive: "
                    "resident keeps the seen-set on device, host_seen "
                    "keeps it in the native host store")
            if self.refiners:
                raise ModeError(
                    "resident mode cannot check refinement PROPERTYs "
                    "(stepwise host checking needs the edge stream) - "
                    "use the level/host_seen device modes")
            if self.live_obligations:
                raise ModeError(
                    "resident mode cannot check temporal properties "
                    "(the behavior graph stays on device) - use the "
                    "level/host_seen device modes")
            # resident dedup keys are always 128-bit fingerprints: the
            # rank-merge binary search and the LSD key sorts are built
            # for a fixed 4-word key
            if not self.fp_mode:
                self.fp_mode = True
                self.K = 4 + 1
        if host_seen:
            from .. import native_store
            if not native_store.is_available():
                raise CompileError(f"host_seen requires the native store: "
                                   f"{native_store.build_error()}")
            if not self.fp_mode:
                # narrow layouts also hash fine; host store is fp-based
                self.fp_mode = True
                self.K = 4 + 1
        # EXPLICIT seen-key mode (ISSUE 12): --seen fingerprint trades
        # exact dedup keys for 128-bit fingerprints on ANY layout (the
        # machinery that always kicked in past FP_THRESHOLD), shrinking
        # the per-state tier footprint (K+1 -> 5 words) by the
        # key-width ratio; the collision-probability bound rides the
        # result.  --seen exact REFUSES configurations that cannot
        # honor it instead of silently fingerprinting.
        if seen_mode not in ("auto", "exact", "fingerprint"):
            raise ModeError(f"unknown --seen mode {seen_mode!r} "
                            f"(expected auto, exact or fingerprint)")
        self.seen_mode_req = seen_mode
        if seen_mode == "fingerprint" and not self.fp_mode:
            self.fp_mode = True
            self.K = 4 + 1
        elif seen_mode == "exact" and self.fp_mode:
            if resident or host_seen:
                raise ModeError(
                    "--seen exact is incompatible with the resident/"
                    "host_seen modes (their dedup machinery is "
                    "fingerprint-based) — use the level device mode")
            raise ModeError(
                f"--seen exact refused: the dedup key is "
                f"{self.key_width} lanes wide (> FP_THRESHOLD="
                f"{FP_THRESHOLD}); exact keys at this width would "
                f"dominate device memory — use --seen fingerprint "
                f"(collision probability is reported) or --backend "
                f"interp")
        # re-stamp after the resident/host_seen fp forcings so the
        # artifact records the dedup mode that actually runs
        tel.gauge("dedup.mode",
                  ("fp128" if self.fp_mode else "exact")
                  + ("-view" if self.view_fn is not None
                     else ("-packed" if not self.plan.identity else "")))
        tel.gauge("seen.mode",
                  "fingerprint" if self.fp_mode else "exact")
        # HIERARCHICAL SEEN SET (ISSUE 12 tentpole): a device seen cap
        # (rows of the key table; --seen-cap, JAXMC_SEEN_CAP is the
        # test knob) turns would-be unbounded device growth into tier
        # SPILL — the sorted device prefix compacts out to host RAM and
        # then disk as immutable sorted runs (backend/tiers.py), and
        # per-level survivors of the device rank-merge binary-search
        # the cold runs before they are counted or explored.  Counts
        # and traces stay bit-identical to the uncapped run.  None =
        # today's grow-forever behavior (no cap, no tiers).
        env_cap = os.environ.get("JAXMC_SEEN_CAP")
        self.seen_cap = int(seen_cap if seen_cap is not None
                            else (env_cap if env_cap else 0)) or None
        if self.seen_cap is not None:
            self.seen_cap = _pow2_at_least(self.seen_cap, lo=64)
            tel.gauge("tier.device_cap", self.seen_cap)
        self.spill_dir = spill_dir or os.environ.get("JAXMC_SPILL_DIR")
        self.host_tier_keys = host_tier_keys
        self._tiers = None  # created lazily at the first spill
        self._cap_breached = None  # rows a soft breach grew the table to
        # LEARNED CAPACITY PROFILE (ISSUE 6): resident runs start at the
        # caps a previous completed run on this (module, layout) ended
        # with — persisted next to the compile cache — so the one
        # warm-up compile covers the whole run and window_recompiles
        # reads 0 on a second run.  Max-merged with any caller hint
        # (bench manifest caps); a stale/foreign profile is ignored with
        # a named profile.status reason (cache.load_capacity_profile).
        if resident and self.cap_profile:
            from ..compile.cache import load_capacity_profile
            prof = load_capacity_profile(
                model.module.name, self._layout_sig(), tel=tel,
                variant=self.backend_desc.profile_variant(),
                optional=("TIERK", "LogCap"))
            if not prof and not self._res_caps_hint:
                # PREDICTED capacity rung (ISSUE 15, below `learned`):
                # a converged bounds fixpoint proves a state-count
                # ceiling, so a COLD first-contact run can size every
                # bucket up front instead of paying growth-retry
                # recompile doublings — window_recompiles reads 0 on
                # fully-proven specs with no saved profile
                pred = self._predicted_caps()
                if pred:
                    self._res_caps_hint = pred
            if prof:
                hint = dict(self._res_caps_hint or {})
                for kk, vv in prof.items():
                    hint[kk] = max(int(hint.get(kk, 0)), vv)
                self._res_caps_hint = hint
                if prof.get("TIERK") and self.seen_cap is not None:
                    # learned tier size (ISSUE 12): a previous
                    # completed run on this (module, layout, platform)
                    # spilled ~TIERK keys — surface the expected
                    # out-of-core magnitude up front so operators and
                    # bench artifacts see it before the first spill
                    tel.gauge("tier.predicted_keys",
                              int(prof["TIERK"]))
                    self.log(f"-- tier: capacity profile predicts an "
                             f"out-of-core run (~{int(prof['TIERK'])} "
                             f"cold-tier keys at the last completion)")

    # ---- predicted capacities (ISSUE 15 tentpole c) -------------------

    def state_estimate(self) -> Optional[int]:
        """analyze's proven state-count ceiling for this model, or None
        (fixpoint bailed / some variable unbounded)."""
        from ..analyze.bounds import BoundsReport, state_space_estimate
        rep = getattr(self.model, "_bounds_report", None)
        if not isinstance(rep, BoundsReport) or not rep.converged:
            return None
        try:
            return state_space_estimate(self.model, rep)
        except Exception:
            if os.environ.get("JAXMC_DEBUG"):
                raise
            return None

    def _predicted_caps(self) -> Optional[Dict[str, int]]:
        """Bounds-sized initial buckets for a cold resident run: the
        capacity-profile ladder's `predicted` rung (below `learned`,
        above the platform defaults).  Only fires when the proven state
        count is small enough that over-allocation is cheap
        (JAXMC_PREDICT_MAX, default 1<<18 states) — a wrong refusal
        costs growth recompiles exactly as before, never memory."""
        est = self.state_estimate()
        cap_max = int(os.environ.get("JAXMC_PREDICT_MAX",
                                     str(1 << 18)))
        if not est or est > cap_max:
            return None
        tel = obs.current()
        caps = {"SC": _pow2_at_least(4 * est, lo=256),
                "FCap": _pow2_at_least(est, lo=64),
                "AccCap": _pow2_at_least(2 * est, lo=128),
                "VC": _pow2_at_least(4 * est, lo=64)}
        tel.gauge("profile.status", "predicted")
        tel.gauge("profile.predicted_states", int(est))
        tel.gauge("profile.predicted_caps", dict(caps))
        self.log(f"-- capacity profile: predicted rung — analyze "
                 f"proves <= {est} states; buckets sized up front "
                 f"(no growth-retry recompiles expected)")
        return caps

    # ---- device persistent-set reduction (ISSUE 18) -------------------

    def _por_plan(self) -> Optional[Dict[str, np.ndarray]]:
        """The device POR plan, or None with the named refusal in
        self.por_reason (the engine then runs UNREDUCED and discloses
        why — same surface as the interp backend's por_refusal path).

        plan = dict(inst_arm [A] int32 — split-arm index per flat
        kernel instance (slotted kernels contribute n_slots entries),
        arm_safe [n_arms] bool — arms the independence report proved
        commuting-with-all + property-invisible).  Memoized: the
        independence analysis walks the AST once per engine."""
        if self._por_memo is not _POR_UNSET:
            return self._por_memo
        from ..analyze.independence import (indep_enabled,
                                            independence_report,
                                            por_refusal)
        plan = None
        reason = None
        if not self.por:
            reason = "POR not requested"
        elif not indep_enabled():
            reason = ("independence analysis disabled "
                      "(JAXMC_ANALYZE_INDEP=0)")
        elif self.hybrid:
            reason = ("hybrid execution: interp-demoted units expand "
                      "on the host where the device mask cannot reach "
                      "them")
        else:
            reason = por_refusal(self.model)
            if reason is None and (self.canon_fn is not None
                                   or self.sym_identity):
                reason = "symmetry canonicalizer active"
            if reason is None:
                try:
                    irep = independence_report(self.model, self.arms)
                except Exception:
                    if os.environ.get("JAXMC_DEBUG"):
                        raise
                    irep = None
                if irep is None:
                    reason = "independence analysis failed"
                elif not irep.por_safe:
                    reason = ("no arm commutes with every other arm "
                              "invisibly")
                else:
                    safe = np.zeros(len(self.arms), dtype=bool)
                    safe[list(irep.por_safe)] = True
                    inst = np.asarray(
                        [self._ca_arm[ci]
                         for ci, ca in enumerate(self.compiled)
                         for _ in range(max(1, ca.n_slots))],
                        np.int32)
                    assert inst.shape[0] == self.A
                    plan = dict(inst_arm=inst, arm_safe=safe)
        self._por_memo = plan
        self.por_reason = reason
        tel = obs.current()
        if self.por:
            if plan is None:
                self.log(f"-- por requested but reduction disabled: "
                         f"{reason} (running unreduced)")
                tel.gauge("por.disabled_reason", reason)
                tel.gauge("por.enabled", False)
            else:
                n_safe = int(plan["arm_safe"].sum())
                self.log(f"-- por: {n_safe}/{len(self.arms)} arms "
                         f"eligible as singleton ample sets (device "
                         f"persistent-set filter in the fused step)")
                tel.gauge("por.enabled", True)
                tel.gauge("por.engine", "device")
        return plan

    def _por_warnings(self) -> List[str]:
        """The interp backend's refusal warning, word-for-word, when
        --por was requested but the reduction cannot run."""
        if not self.por:
            return []
        if self._por_plan() is None:
            return [f"--por requested but reduction disabled: "
                    f"{self.por_reason} (running unreduced)"]
        return []

    def _por_finish(self, ample: int, expanded: int, masked: int,
                    distinct: int) -> None:
        """Emit the end-of-run POR counters (same names as the interp
        engine, plus the device-only masked-candidate gauge)."""
        if not self.por or self._por_memo in (None, _POR_UNSET):
            return
        tel = obs.current()
        full = max(0, int(expanded) - int(ample))
        tel.counter("por.ample_states", int(ample))
        tel.counter("por.full_states", full)
        tel.gauge("por.ample_ratio",
                  round(int(ample) / int(expanded), 4)
                  if expanded else 0.0)
        tel.gauge("por.device_masked_arms", int(masked))
        tel.gauge("por.reduced_states", int(distinct))

    # ---- lifted constants + follower clones (ISSUE 13) ---------------

    def _install_const_lanes(self, cvec) -> None:
        """Bind the lifted-constant TRACERS into the kernel context for
        the duration of a trace (kernel2 identifier resolution reads
        kc.const_lanes).  No-op for ordinary engines."""
        if self._lift_names:
            self.kc.const_lanes = {
                nm: cvec[i] for i, nm in enumerate(self._lift_names)}

    def _traced_with(self, fn, cvec, *args):
        """Run `fn(*args)` (a trace) with const lanes installed; used
        by the forced abstract traces at build time."""
        self._install_const_lanes(cvec)
        try:
            return fn(*args)
        finally:
            self.kc.const_lanes = {}

    def _cvec_jnp(self):
        if getattr(self, "_cvec_dev", None) is None:
            self._cvec_dev = jnp.asarray(self._cvec)
        return self._cvec_dev

    def batch_block_reason(self) -> Optional[str]:
        """None when this engine can serve as a cross-model batch
        donor/member; otherwise the human-readable blocker (the batch
        planner falls back to solo runs and reports it)."""
        if not self.host_seen:
            return "host_seen mode required"
        if self.hybrid:
            return ("hybrid execution (interp-demoted units): "
                    + "; ".join(
                        [f"arm {a.label or 'Next'}" for a, _ in
                         self.fb_arms]
                        + [f"invariant {nm}" for nm, _, _ in
                           self.fb_invs]
                        + [f"constraint {nm}" for nm, _, _ in
                           self.fb_cons]))
        if self.refiners:
            return "refinement PROPERTYs (stepwise host edge checks)"
        if self.live_obligations:
            return "temporal PROPERTYs (behavior graph)"
        if self._demotable:
            # a fired compile-recovery demotion restarts via
            # _demote_arms, which MUTATES the (donor-shared) compiled
            # arm set mid-cohort — refuse up front; the jobs run solo
            # where the demotion restart is sound
            return ("compile-recovery demotions possible (arms "
                    + ", ".join(self.arms[i].label or "Next"
                                for i in self._demotable)
                    + "): a runtime demotion restart would mutate the "
                      "shared batch program")
        if self.seen_cap is not None:
            return "hierarchical seen-set spill (per-member tiers)"
        fused_max = int(os.environ.get("JAXMC_FUSED_MAX_INSTANCES",
                                       "24"))
        if jax.default_backend() == "cpu" and self.A > fused_max:
            return (f"arm-split step ({self.A} instances > "
                    f"JAXMC_FUSED_MAX_INSTANCES={fused_max})")
        return None

    _DONOR_SHARED = (
        "backend_desc", "bounds", "layout", "kc", "plan", "compiled",
        "actions", "arms", "_ca_arm", "fb_arms", "fb_invs", "fb_cons",
        "inv_fns", "constraint_fns", "canon_fn", "_sym_fallback",
        "sym_identity", "sym_form", "view_fn", "view_width", "refiners",
        "unrefined", "live_obligations", "live_unsupported",
        "collect_edges", "hybrid", "_demotable", "labels_flat",
        "arm_verdicts", "A", "W", "PW", "K", "fp_mode", "key_width",
        "donate", "chunk", "sample_cfg", "host_seen", "seen_mode_req",
        "_lift_names", "_trace_lock",
        # compiled-program caches are SHARED OBJECTS: a follower's
        # first dispatch is a cache hit on the donor's jit, with its
        # own constant vector as a runtime argument
        "_step_cache", "_hstep_cache", "_hstep_group_jits",
        "_newcheck_cache", "_res_cache", "_hostkeys_cache",
        "_pkeys_cache")

    def _clone_from_donor(self, donor: "TpuExplorer", model: Model,
                          log, max_states, store_trace, progress_every,
                          checkpoint_path, checkpoint_every,
                          resume_from, final_checkpoint,
                          inits=None) -> None:
        """FOLLOWER construction (ISSUE 13): reuse the donor's layout
        and compiled kernels wholesale — zero sampling, zero bounds
        fixpoint, zero kernel builds — binding only this member's
        model, init states and run-control surface.  The caller
        (backend/batch.py) has already proven layout compatibility
        (same module shape; constants outside the lifted set equal) and
        that the donor is batchable (no hybrid units, no refiners, no
        temporal obligations)."""
        reason = donor.batch_block_reason()
        if reason is not None:
            raise ModeError(f"donor engine is not batchable: {reason}")
        for attr in self._DONOR_SHARED:
            setattr(self, attr, getattr(donor, attr))
        self.model = model
        self.log = log if log is not None else obs.Logger(quiet=True)
        self.max_states = max_states
        self.store_trace = store_trace
        self.progress_every = progress_every
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = checkpoint_every
        self.resume_from = resume_from
        self.final_checkpoint = final_checkpoint
        self.resident = False
        self.pin_interp_arms = False
        self.extra_samples = []
        # relayout/demotion restarts rebuild layout+kernels per member,
        # which would diverge from the shared batch program: a follower
        # that hits a recovery abort surfaces it (the batch runner
        # falls back to a solo re-run)
        self.relayouts_left = 0
        self.cap_profile = False
        self._res_caps_hint = None
        self._res_caps = None
        self._res_maxlvl = donor._res_maxlvl
        self._last_frontier_np = None
        self.seen_cap = None
        self.spill_dir = None
        self.host_tier_keys = None
        self._tiers = None
        self._cap_breached = None
        self._cvec = np.asarray([int(model.defs[n])
                                 for n in self._lift_names], np.int32)
        self._cvec_dev = None
        # a follower built no layout: it encodes its own initial states
        self._init_walks0 = None
        self._init_rows_built = None
        self.init_states = self._enumerate_init(inits)

    def _enumerate_init(self, inits: Optional[List[Dict[str, Any]]]
                        ) -> List[Dict[str, Any]]:
        """The model's initial states: `inits` where the caller has
        already walked Init (a cohort samples its members before their
        engines exist), else one walk, under span `init_enumerate`."""
        if inits is not None:
            return inits
        model = self.model
        with obs.current().span("init_enumerate") as sp:
            inits = enumerate_init(model.init, model.ctx(), model.vars)
            sp.attrs["states"] = len(inits)
        return inits

    def _expand_fn(self, scope: str = "jaxmc.expand"):
        """The (state x action) expansion closure shared by both step
        builders; slotted kernels vmap over a traced slot index.
        `scope` names its device operations in a trace (the trace walk
        re-expands under its own name: a reader takes the innermost)."""
        acts = self.compiled
        if not acts:
            # hybrid with every arm demoted: a zero-instance expansion
            # (jnp.stack refuses empty lists; shapes stay [0, FC(, W)])
            W = self.W

            @jax.named_scope(scope)
            def expand_none(frontier):
                FC = frontier.shape[0]
                z = jnp.zeros((0, FC), bool)
                return (z, jnp.ones((0, FC), bool),
                        jnp.zeros((0, FC), jnp.int32),
                        jnp.zeros((0, FC, W), jnp.int32))

            return expand_none

        @jax.named_scope(scope)
        def expand(frontier):
            ens, aoks, ovs, succs = [], [], [], []
            for ca in acts:
                if ca.n_slots:
                    slots = jnp.arange(ca.n_slots, dtype=jnp.int32)
                    en, aok, ov, succ = jax.vmap(
                        jax.vmap(ca.fn, in_axes=(0, None)),
                        in_axes=(None, 0))(frontier, slots)
                    for si in range(ca.n_slots):
                        ens.append(en[si])
                        aoks.append(aok[si])
                        ovs.append(ov[si])
                        succs.append(succ[si])
                else:
                    en, aok, ov, succ = jax.vmap(ca.fn)(frontier)
                    ens.append(en)
                    aoks.append(aok)
                    ovs.append(ov)
                    succs.append(succ)
            return (jnp.stack(ens), jnp.stack(aoks), jnp.stack(ovs),
                    jnp.stack(succs))

        return expand

    def _candidate_block_fn(self, FC: int):
        """Shared mesh-step prologue (ISSUE 8): expand one frontier
        block of capacity FC and produce the flat candidate block with
        its dedup keys, packed rows and fault scalars.  Both mesh step
        builders (the legacy exchange step and the device-resident
        level step, backend/mesh.py) start from exactly this closure so the
        candidate semantics — validity masking, pack-guard overflow
        folding (OV_PACK under kernel codes), assert/deadlock
        provenance — cannot drift between them.

        Returns a closure (frontier_lanes, fvalid) -> dict with keys:
          gen_local, overflow (max OV_* code, 0 = none),
          ckeys [C,K], cand [C,PW] packed, cand_u [C,W], cvalid [C],
          dead [FC] bool, dead_slot, assert_bad (scalar), asrt_a, asrt_f
        where C = A * FC."""
        A, W = self.A, self.W
        C = A * FC
        keys_of = self._keys_of
        expand = self._expand_fn()

        def block(frontier, fvalid):
            en, aok, ov, succ = expand(frontier)
            valid = en & fvalid[None, :]
            abad = (~aok) & fvalid[None, :]
            assert_bad = jnp.any(abad)
            aflat = jnp.argmax(abad.reshape(-1))
            asrt_a = (aflat // FC).astype(jnp.int32)
            asrt_f = (aflat % FC).astype(jnp.int32)
            overflow = jnp.max(jnp.where(fvalid[None, :], ov, 0)) \
                .astype(jnp.int32)
            dead = fvalid & ~jnp.any(en, axis=0)
            dead_slot = jnp.argmax(dead).astype(jnp.int32)
            gen_local = jnp.sum(valid)
            cand_u = succ.reshape(C, W)
            cvalid = valid.reshape(C)
            cand_u = jnp.where(cvalid[:, None], cand_u, SENTINEL)
            ckeys, cand, pack_ovf = keys_of(cand_u, cvalid)
            overflow = jnp.where(
                overflow != 0, overflow,
                jnp.where(pack_ovf, OV_PACK, 0).astype(jnp.int32))
            return dict(gen_local=gen_local, overflow=overflow,
                        ckeys=ckeys, cand=cand, cand_u=cand_u,
                        cvalid=cvalid, dead=dead, dead_slot=dead_slot,
                        assert_bad=assert_bad, asrt_a=asrt_a,
                        asrt_f=asrt_f)

        return block

    def _temporal_warnings(self) -> List[str]:
        out = []
        if self.live_unsupported:
            out.append(
                "temporal properties NOT checked (unsupported form): "
                + ", ".join(self.live_unsupported))
        for rc in self.refiners:
            if rc.liveness_skipped:
                out.append(
                    f"property {rc.name}: refinement checked stepwise; "
                    f"its fairness conjuncts are NOT checked")
        return out

    def _check_live(self, graph, warnings) -> Optional[Violation]:
        """Run the temporal obligations over the accumulated behavior
        graph (end of a completed search)."""
        if not self.live_obligations:
            return None
        from ..engine.liveness import LivenessChecker
        states = [self.layout.decode_packed(r) for r in graph.rows]
        lc = LivenessChecker(self.model, states, graph.edges,
                             graph.parents, graph.labels)
        bad, live_warns = lc.check(self.live_obligations)
        warnings.extend(live_warns)
        if bad is None:
            return None
        pname, trace, msg = bad
        return Violation("property", pname, trace, msg)

    def _refine_init(self, init_rows, explored_init):
        """check_init on kept init states; (rc_name, state) | None."""
        if not self.refiners:
            return None
        for i in explored_init:
            st = self.layout.decode(init_rows[i])
            for rc in self.refiners:
                if not rc.check_init(st):
                    return rc.name, st
        return None

    def _refine_edges(self, frontier_rows, cand, cvalid, explore, FC):
        """Stepwise refinement over this level's kept candidate edges
        (decode on host, same check the interp engine runs). Returns
        (action_idx, frontier_idx, succ_state, checker) or None.
        Duplicate (parent, succ) pairs are checked once per run."""
        if not self.refiners:
            return None
        idxs = np.nonzero(np.asarray(cvalid) & np.asarray(explore))[0]
        if not len(idxs):
            return None
        cand = np.asarray(cand)
        frontier_rows = np.asarray(frontier_rows)
        parents: Dict[int, Any] = {}
        if len(self._ref_pair_cache) > (1 << 20):
            self._ref_pair_cache.clear()
        for c in idxs:
            f = int(c % FC)
            a = int(c // FC)
            key = (frontier_rows[f].tobytes(), cand[c].tobytes())
            if key in self._ref_pair_cache:
                continue
            self._ref_pair_cache.add(key)
            pst = parents.get(f)
            if pst is None:
                pst = self.layout.decode_packed(frontier_rows[f])
                parents[f] = pst
            sst = self.layout.decode_packed(cand[c])
            for rc in self.refiners:
                if not rc.check_edge(pst, sst):
                    return a, f, sst, rc
        return None

    def _refine_msg(self, rc) -> str:
        msg = (f"step is not a [{rc.name}-Next]_v step of the refined "
               f"specification")
        if rc.last_error:
            msg += f"; while evaluating the property: {rc.last_error}"
        return msg

    def _refine_violation(self, rc, sst, a, trace):
        trace = [x for x in trace if x[0] is not None]
        trace.append((sst, self.labels_flat[a]))
        return Violation("property", rc.name, trace, self._refine_msg(rc))

    def _symmetry_warnings(self) -> List[str]:
        if self.model.symmetry is None or self.canon_fn is not None \
                or self.sym_identity:
            # identity groups have no reduction to fall back FROM:
            # counts match the (equally unreduced) TLC/interp search,
            # so warning of divergence would be wrong in kind
            return []
        return [SYMMETRY_WARNING + (f" ({self._sym_fallback})"
                                    if self._sym_fallback else "")]

    def _keys_of(self, rows, valid):
        """`_keys_of_rows` over this engine's plan, view, symmetry and
        key mode."""
        return _keys_of_rows(self.plan, self.view_fn, self.canon_fn,
                             self.fp_mode, rows, valid)

    def _keys_fn(self):
        """`_keys_of` as a closure over the four things it reads and
        not over the engine: a program the registry keeps
        (`_held_program`) then pins kernels, not the engine that made
        it with its init states and tables."""
        return partial(_keys_of_rows, self.plan, self.view_fn,
                       self.canon_fn, self.fp_mode)

    def _canon_host(self, rows_np: np.ndarray) -> np.ndarray:
        """The canonicaliser over host rows (the initial states), numpy in
        and out, through ONE jitted program in blocks of at most 2^16
        rows: a cfg whose Init is a function space hands over a quarter
        of a million rows, and an eager call traces op by op at that
        size."""
        n = len(rows_np)
        blk = min(_pow2_at_least(n, lo=8), 1 << 16)
        jf = jax.jit(self.canon_fn)
        out = np.empty((n, self.W), np.int32)
        buf = np.empty((blk, self.W), np.int32)
        for i in range(0, n, blk):
            part = rows_np[i:i + blk]
            buf[:len(part)] = part
            buf[len(part):] = part[:1]
            out[i:i + blk] = np.asarray(jf(buf))[:len(part)]
        return out

    def _host_keys(self, rows_np):
        """Host-side (keys, packed, pack_ovf) over unpacked numpy rows —
        the init/fallback boundary paths.  numpy in, numpy out.  Jitted
        per power-of-two bucket: the eager op-by-op dispatch of the
        pack + fingerprint chain costs ~20ms even for a handful of rows
        (measured on viewtoy), which dominated warm whole-run walls."""
        n = len(rows_np)
        if n == 0:
            return (np.zeros((0, self.K), np.int32),
                    np.zeros((0, self.PW), np.int32), False)
        cap = _pow2_at_least(n, lo=8)
        jf = self._hostkeys_cache.get(cap)
        if jf is None:
            keys_of = self._keys_fn()
            jf = self._held_program(
                "bfs.host_keys", cap, lambda: obs.prof_wrap(
                    "bfs.host_keys", jax.jit(
                        lambda rows, valid: keys_of(rows, valid)),
                    key=cap))
            self._hostkeys_cache[cap] = jf
        buf = np.repeat(np.asarray(rows_np[:1], np.int32), cap, axis=0)
        buf[:n] = rows_np
        k, p, o = jf(jnp.asarray(buf),
                     jnp.asarray(np.arange(cap) < n))
        return np.asarray(k)[:n], np.asarray(p)[:n], bool(o)

    # ---- hierarchical seen set (ISSUE 12): spill + cold-tier probes --

    def _ensure_tiers(self):
        """The cold-tier store, created at the first spill (zero cost —
        and zero behavior change — for runs that never overflow)."""
        if self._tiers is None:
            from .tiers import TieredSeen
            self._tiers = TieredSeen(
                self.K - 1, host_budget_keys=self.host_tier_keys,
                spill_dir=self.spill_dir, log=self.log)
        return self._tiers

    def _begin_search(self) -> None:
        """Cold tiers and a soft breach of the cap are state of ONE
        search: an engine that has searched before starts the next with
        no cold run (a `--resume` then loads what its checkpoint
        carries, `_load_ck`).  Left in place they would answer the next
        search's frontier with the last one's states."""
        if self._tiers is not None:
            self._tiers.reset()
        self._cap_breached = None

    @staticmethod
    def _device_table(shape, head: Optional[np.ndarray] = None,
                      fill=SENTINEL, sharding=None):
        """A table of `fill` rows (a word, or a tuple of one word per
        lane of the last axis) with `head` in its leading corner, made
        ON the device: the host hands over the head alone (KBs) where
        `np.full` + upload is a fresh, page-faulting host buffer of the
        table's size per search (1 ns a byte, ISSUE 35) and a transfer
        the device waits for.  Every search start, resume and spill of
        all three engines makes its capacity-sized tables here
        (`_table_program`: fill and head in one buffer, enqueued and not
        waited for, compiled once per shape).  `sharding`: the mesh's,
        so that each device fills its own shard of a [D, ...] table."""
        shape = tuple(int(n) for n in shape)
        if head is not None:
            head = np.asarray(head, np.int32)
            if head.ndim != len(shape) or any(
                    h > n for h, n in zip(head.shape, shape)):
                # the update would be clamped into the table, silently
                raise ValueError(f"a head of shape {head.shape} does "
                                 f"not fit a table of shape {shape}")
            head = jax.device_put(head, sharding) if head.size else None
        fill = tuple(int(w) for w in fill) if np.ndim(fill) else int(fill)
        return _table_program(sharding)(head, shape=shape, fill=fill)

    def _seed_tables(self, SC: int, FC: int, seen_head, fr_head):
        """The one-chip engines' two search tables, [SC, K] seen and
        [FC, PW] frontier, made on the device from their heads (the init
        rows, or a checkpoint's).  `search.seed_bytes` counts what the
        host handed over: the heads."""
        obs.current().counter("search.seed_bytes",
                              fr_head.nbytes + seen_head.nbytes)
        frontier = self._device_table((FC, self.PW), fr_head)
        return self._device_table((SC, self.K), seen_head), frontier

    def _tier_spill(self, seen, count: int):
        """Compact the device table's sorted valid prefix out as ONE
        immutable sorted run (the validity lane is stripped — cold runs
        hold data words only) and hand back an empty table of the same
        capacity."""
        tel = obs.current()
        with tel.span("tier.spill", keys=count, bytes=int(seen.nbytes)):
            self._ensure_tiers().spill(
                np.ascontiguousarray(np.asarray(seen)[:count, 1:]))
            tel.counter("tier.spilled_keys", int(count))
            return self._device_table(seen.shape)

    def _note_cap_breach(self, rows: int, why: str) -> None:
        """A soft breach made visible: the device table grew past the
        cap because it must seat a level's candidates beside nothing
        (`seen_count + candidates <= SC`), so the cap held less than it
        says.  Gauge `tier.cap_breached` and `result.tiers` carry the
        rows it grew to."""
        self._cap_breached = max(self._cap_breached or 0, int(rows))
        obs.current().gauge("tier.cap_breached", self._cap_breached)
        self.log(f"-- tier: device cap {self.seen_cap} < {why}; growing "
                 f"to {int(rows)} rows anyway (soft cap, "
                 f"tier.cap_breached)")

    def _packed_keys(self, packed_np: np.ndarray) -> np.ndarray:
        """Dedup-key DATA words ([n, K-1], validity lane stripped) for a
        block of PACKED rows — the cold-tier probe basis for frontier
        rows pulled back from the device.  Jitted per power-of-two
        bucket like _host_keys."""
        n = len(packed_np)
        if n == 0:
            return np.zeros((0, self.K - 1), np.int32)
        cap = _pow2_at_least(n, lo=64)
        jf = self._pkeys_cache.get(cap)
        if jf is None:
            plan = self.plan
            keys_of = self._keys_of

            @jax.jit
            def pk(packed, valid):
                rows = plan.unpack_rows(packed)
                return keys_of(rows, valid)[0]

            self._pkeys_cache[cap] = jf = obs.prof_wrap(
                "bfs.packed_keys", pk, key=cap)
        buf = np.repeat(np.asarray(packed_np[:1], np.int32), cap, axis=0)
        buf[:n] = packed_np
        k = jf(jnp.asarray(buf), jnp.asarray(np.arange(cap) < n))
        return np.asarray(k)[:n, 1:]

    def _tier_keep_mask(self, rows_np: np.ndarray) -> np.ndarray:
        """[n] bool keep-mask over packed rows: False where the row's
        dedup key already lives in a cold tier (it was admitted before
        the spill, so the uncapped run would never have re-frontiered
        it)."""
        if self._tiers is None or not self._tiers.active \
                or len(rows_np) == 0:
            return np.ones(len(rows_np), bool)
        with obs.current().span("tier.keys", rows=len(rows_np)):
            keys = self._packed_keys(rows_np)
        return ~self._tier_probe(keys)

    def _tier_probe(self, keys: np.ndarray) -> np.ndarray:
        """[n] bool, True where a key lives in a cold run; the probe's
        span and its three counters."""
        tel = obs.current()
        verified = self._tiers.keys_verified
        with tel.span("tier.probe", keys=len(keys),
                      runs=len(self._tiers.host_runs)
                      + len(self._tiers.disk_runs)):
            dup = self._tiers.probe(keys)
        tel.counter("tier.keys_probed", len(keys))
        tel.counter("tier.keys_dropped", int(dup.sum()))
        tel.counter("tier.keys_verified",
                    self._tiers.keys_verified - verified)
        return dup

    # ---- jitted level step, compiled per (seen_cap, frontier_cap) ----
    def _get_step(self, SC: int, FC: int) -> Callable:
        # tiered runs (ISSUE 12) also stream each kept row's dedup key
        # to the host, so the cold-tier membership probe never
        # recomputes keys; the flag joins the compile key — the one
        # recompile it costs happens at the first spill
        tiered = self._tiers is not None and self._tiers.active
        # device POR (ISSUE 18): the persistent-set filter joins the
        # compile key — the mask arrays are baked constants
        por_plan = self._por_plan() if self.por else None
        por = por_plan is not None
        key = (SC, FC, tiered, por)
        if key in self._step_cache:
            obs.current().counter("compile.cache_hits")
            return self._step_cache[key]
        obs.current().counter("compile.cache_misses")
        A, W, K, PW = self.A, self.W, self.K, self.PW
        plan = self.plan
        inv_fns = self.inv_fns
        con_fns = self.constraint_fns
        keys_of = self._keys_of
        expand = self._expand_fn()
        # stream candidates for stepwise refinement and/or the liveness
        # behavior graph on the host (verdict parity with the interp)
        need_edges = bool(self.refiners) or self.collect_edges
        if por:
            # temporal/refinement PROPERTYs are por_refusal territory,
            # so the edge stream and the mask can never co-occur
            assert not need_edges
            por_inst = jnp.asarray(por_plan["inst_arm"])
            por_safe_v = jnp.asarray(por_plan["arm_safe"])
        # FUSED + DONATED level step (ISSUE 6): the whole level —
        # expansion, fingerprint/pack, dedup sort, CONSTRAINT and
        # invariant evaluation — is ONE jitted dispatch, and the seen
        # table (always) plus the frontier (unless the run streams
        # edges, which reads the frontier after the step) are donated so
        # XLA updates them in place instead of copying per level.
        donate = (0, 2) if self.donate and not need_edges \
            else ((0,) if self.donate else ())

        @partial(jax.jit, donate_argnums=donate)
        def step(seen_keys, seen_count, frontier_p, fcount):
            with jax.named_scope("jaxmc.expand"):
                frontier = plan.unpack_rows(frontier_p)
                fvalid = jnp.arange(FC) < fcount
                en, aok, ov, succ = expand(frontier)
                valid = en & fvalid[None, :]
                assert_bad = (~aok) & fvalid[None, :]
                # ov carries the int overflow CODE (kernel2.OV_*): keep the
                # max so the engine can tell demotion aborts from capacity
                overflow = jnp.where(fvalid[None, :], ov, 0)
                dead = fvalid & ~jnp.any(en, axis=0)
                gen = jnp.sum(valid)

                C = A * FC
                cand_u = succ.reshape(C, W)
                cvalid = valid.reshape(C)
                prov = jnp.arange(C, dtype=jnp.int32)
                cand_u = jnp.where(cvalid[:, None], cand_u, SENTINEL)
            ckeys, cand, pack_ovf = keys_of(cand_u, cvalid)

            por_ample = por_expanded = por_masked = jnp.int32(0)
            if por:
                # persistent-set filter INSIDE the fused step (ISSUE
                # 18): probe the PRE-level seen snapshot (closure
                # through this depth — see _por_mask for the cycle-
                # proviso argument), then mask every non-ample arm's
                # candidates.  Deadlock/assert verdicts above read the
                # PRE-mask enabledness; gen counts the reduced stream.
                found, _ = _seen_probe(seen_keys, seen_count, ckeys, SC)
                keep, por_ample, por_expanded = _por_mask(
                    found, cvalid, por_inst, por_safe_v, A, FC)
                with jax.named_scope("jaxmc.expand"):
                    por_masked = jnp.sum(cvalid & ~keep, dtype=jnp.int32)
                    inv_key = jnp.concatenate([
                        jnp.ones((C, 1), jnp.int32),
                        jnp.full((C, K - 1), SENTINEL, jnp.int32)], axis=1)
                    ckeys = jnp.where(keep[:, None], ckeys, inv_key)
                    cand_u = jnp.where(keep[:, None], cand_u, SENTINEL)
                    cvalid = keep
                    gen = jnp.sum(keep)

            # O(new): the seen table keeps a sorted valid prefix (init
            # lexsorts, the merge writes sorted output), so only the C
            # candidate keys are sorted; the VALID ones are deduped
            # against that prefix with binary searches (a block of C/64
            # queries at a time, rounds from seen_count: the work
            # follows gen, not A x FC) and the new keys merged in by
            # rank — rows fetched by gather, not scattered (a row
            # scatter cost 9-27x a row gather on the v5e; ledger, PR
            # 24), into the blocks of the DONATED table that hold a
            # live row after the level and no other (in place: the
            # work follows seen_count2, not SC).  nk_sidx is each new
            # key's original candidate index in key-sorted order
            # (stable ties keep the first occurrence).  The caller
            # pre-grows SC so seen_count + C <= SC: seen_count2 never
            # overflows.
            rm = _rank_merge(seen_keys, seen_count, ckeys, C, SC, K,
                             multikey=True)
            new_count = rm["new_count"]
            safe_cidx = jnp.clip(rm["nk_sidx"], 0, C - 1)
            seen2 = rm["seen2"]
            seen_count2 = rm["seen_count2"]

            with jax.named_scope("jaxmc.compact"):
                new_rows = jnp.take(cand, safe_cidx, axis=0)      # packed
                new_rows_u = jnp.take(cand_u, safe_cidx, axis=0)  # lanes
                new_prov = jnp.take(prov, safe_cidx)
                nvalid = jnp.arange(C) < new_count
                new_rows = jnp.where(nvalid[:, None], new_rows, SENTINEL)

            # a scope of its own where the cfg has a CONSTRAINT, so that a
            # trace prices the branch (ISSUE 51); a program without one
            # keeps the name it had
            with jax.named_scope("jaxmc.constraint" if con_fns
                                 else "jaxmc.scan"):
                # constraints FIRST: violating states are fingerprinted (they
                # are in seen2 above) but discarded — never counted distinct,
                # never invariant-checked, never explored. TLC semantics,
                # pinned by the golden run (testout2:265, 195 distinct)
                explore = nvalid
                for nm, f in con_fns:
                    explore = explore & jax.vmap(f)(new_rows_u)
                explore_count = jnp.sum(explore)
            with jax.named_scope("jaxmc.compact"):
                # the next frontier is ordered by PROVENANCE (frontier-slot
                # major, action minor — the interpreter's discovery order),
                # not by dedup-key order: key order depends on the packed
                # encoding, so ordering by it would let the bit layout pick
                # WHICH equally-short counterexample gets reported (packed
                # and unpacked runs must produce identical traces)
                fmaj = (new_prov % FC) * jnp.int32(max(A, 1)) + \
                    new_prov // FC
                idx4 = jnp.arange(C, dtype=jnp.int32)
                ops4 = ((1 - explore.astype(jnp.int32)), fmaj, idx4)
                comp4 = lax.sort(ops4, num_keys=2, is_stable=True)
                perm4 = comp4[2]
                front_rows = jnp.take(new_rows, perm4, axis=0)
                front_rows_u = jnp.take(new_rows_u, perm4, axis=0)
                front_prov = jnp.take(new_prov, perm4)
                frontvalid = jnp.arange(C) < explore_count
                front_keys = None
                if tiered:
                    new_keys = jnp.take(ckeys, safe_cidx, axis=0)
                    front_keys = jnp.take(new_keys, perm4, axis=0)

            with jax.named_scope("jaxmc.scan"):
                # invariants over the kept (explored) states only
                inv_bad_any = jnp.asarray(False)
                inv_bad_idx = jnp.asarray(0, jnp.int32)
                inv_bad_which = jnp.asarray(-1, jnp.int32)
                for wi, (nm, f) in enumerate(inv_fns):
                    ok = jax.vmap(f)(front_rows_u)
                    bad = frontvalid & ~ok
                    any_ = jnp.any(bad)
                    idx = jnp.argmax(bad)
                    first = jnp.logical_and(any_, ~inv_bad_any)
                    inv_bad_idx = jnp.where(first, idx, inv_bad_idx)
                    inv_bad_which = jnp.where(first, wi, inv_bad_which)
                    inv_bad_any = inv_bad_any | any_

            # kernel overflow codes outrank the pack guard: OV_DEMOTED
            # must reach the engine so the hybrid restart can fire
            base_ov = jnp.max(overflow, initial=0)
            ov_out = jnp.where(base_ov != 0, base_ov,
                               jnp.where(pack_ovf, OV_PACK, 0))
            out = dict(gen=gen, dead=dead, assert_bad=assert_bad,
                       overflow=ov_out,
                       seen=seen2, seen_count=seen_count2,
                       front_rows=front_rows, front_prov=front_prov,
                       front_count=explore_count,
                       inv_bad_any=inv_bad_any, inv_bad_idx=inv_bad_idx,
                       inv_bad_which=inv_bad_which)
            if por:
                out["por_ample"] = por_ample
                out["por_expanded"] = por_expanded
                out["por_masked"] = por_masked
            if front_keys is not None:
                out["front_keys"] = front_keys
            if need_edges:
                with jax.named_scope("jaxmc.constraint" if con_fns
                                     else "jaxmc.scan"):
                    exp_all = cvalid
                    for nm, f in con_fns:
                        exp_all = exp_all & jax.vmap(f)(cand_u)
                out["cand"] = cand
                out["cvalid"] = cvalid
                out["explore_all"] = exp_all
            return out

        step = obs.prof_wrap("bfs.level_step", step, key=key)
        self._step_cache[key] = step
        return step

    def _hstep_core(self, FC: int) -> Callable:
        """The UNJITTED fused host_seen step:
        (frontier_p [FC, PW], fcount, cvec [n_lift] i32) -> out dict.
        One unit, two compilers: the solo engine jits it directly
        (_get_hstep), the cross-model batcher (backend/batch.py) jits
        jax.vmap of it so B members' frontiers + per-model constant
        vectors go through ONE dispatch.  `cvec` is the lifted-constant
        vector (empty for ordinary engines); the tracer install at the
        top is what makes the compiled program constant-generic."""
        A, W = self.A, self.W
        plan = self.plan
        inv_fns = self.inv_fns
        con_fns = self.constraint_fns
        keys_of = self._keys_of
        install = self._install_const_lanes

        def hstep_core(frontier_p, fcount, cvec):
            install(cvec)
            frontier = plan.unpack_rows(frontier_p)
            fvalid = jnp.arange(FC) < fcount
            en, aok, ov, succ = self._expand_fn()(frontier)
            valid = en & fvalid[None, :]
            assert_bad = (~aok) & fvalid[None, :]
            # int overflow CODE (kernel2.OV_*), max-reduced below
            overflow = jnp.where(fvalid[None, :], ov, 0)
            dead = fvalid & ~jnp.any(en, axis=0)
            gen = jnp.sum(valid)
            C = A * FC
            cand_u = succ.reshape(C, W)
            cvalid = valid.reshape(C)
            cand_u = jnp.where(cvalid[:, None], cand_u, SENTINEL)
            keys, cand, pack_ovf = keys_of(cand_u, cvalid)
            inv_ok = jnp.ones(C, bool)
            for nm, f in inv_fns:
                inv_ok = inv_ok & jax.vmap(f)(cand_u)
            explore = jnp.ones(C, bool)
            for nm, f in con_fns:
                explore = explore & jax.vmap(f)(cand_u)
            base_ov = jnp.max(overflow, initial=0)
            ov_out = jnp.where(base_ov != 0, base_ov,
                               jnp.where(pack_ovf, OV_PACK, 0))
            # trace hygiene: clear the shared ctx so no stale tracers
            # outlive this trace (every read happened above)
            self.kc.const_lanes = {}
            return dict(cand=cand, cvalid=cvalid, keys=keys, gen=gen,
                        dead=dead, assert_bad=assert_bad,
                        overflow=ov_out,
                        inv_ok=inv_ok, explore=explore)

        return hstep_core

    def _get_hstep(self, FC: int) -> Callable:
        """Expand-only step for host_seen mode: the seen-set lives in the
        native C++ fingerprint store (native/fps_store.cc) — the spill
        layer of SURVEY.md §7.5 — so the device does expansion, hashing,
        and predicate checks while membership runs on the host."""
        if FC in self._hstep_cache:
            obs.current().counter("compile.cache_hits")
            return self._hstep_cache[FC]
        obs.current().counter("compile.cache_misses")
        A, W, PW = self.A, self.W, self.PW
        plan = self.plan
        con_fns = self.constraint_fns
        keys_of = self._keys_of

        # SPLIT vs FUSED compilation (VERDICT r3 weak #3, retuned by
        # ISSUE 6): one fused jit over all A kernels compiles
        # superlinearly on XLA:CPU (MCVoting's 60 instances: >10 min
        # fused vs ~2 min as 60 small programs + one tiny combine) — but
        # always-split-on-CPU made every SMALL model pay A dispatches +
        # a combine + deferred predicate dispatches per chunk, one of
        # the constant factors behind the r04 kernel-slower-than-interp
        # inversion.  The fused step (expansion + predicates + pack +
        # fingerprint in ONE dispatch per chunk) is now the default
        # whenever the instance count is modest; only many-instance
        # models split on CPU (JAXMC_FUSED_MAX_INSTANCES, default 24).
        fused_max = int(os.environ.get("JAXMC_FUSED_MAX_INSTANCES",
                                       "24"))
        split = jax.default_backend() == "cpu" and A > fused_max \
            and not self._lift_names

        if not split:
            core_j = obs.prof_wrap("bfs.hstep",
                                   jax.jit(self._hstep_core(FC)), key=FC)
            cvec = self._cvec_jnp()

            def hstep(frontier_p, fcount):
                return core_j(frontier_p, fcount, cvec)

            hstep.is_async = True  # fused jit: dispatch is asynchronous
            self._hstep_cache[FC] = hstep
            return hstep

        # ARM-GROUP fused jits (ISSUE 7 satellite, lifting the ROADMAP
        # item-2 remainder): the old fallback compiled one jit PER
        # ACTION (A dispatches + A host round-trips per chunk — pure
        # overhead, the r04 inversion's constant factor writ large on
        # many-instance models).  Instead, partition the compiled
        # actions into groups of <= fused_max INSTANCES and fuse each
        # group into ONE jit: XLA:CPU's superlinear fused-compile cost
        # stays bounded by the group size while the dispatch count
        # drops from A to ceil(A/fused_max).  Candidate order is
        # preserved (groups are contiguous in self.compiled order and
        # concatenate in order), so counts and traces stay identical
        # to both the per-action and the fully-fused paths.
        #
        # Predicates are NOT evaluated per candidate here: the engine
        # only consults inv_ok/explore on NEW rows (a handful per level)
        # — MCVoting's quantifier-heavy Inv over every one of the
        # A*CH = 123k padded candidates per chunk was the r3 sweep's
        # >900 s timeout. The per-candidate explore mask is computed
        # only when the edge stream needs it (refinement/liveness).
        acts = self.compiled
        need_edges = bool(self.refiners) or self.collect_edges

        @jax.jit
        def combine(cand_u, cvalid):
            cand_u = jnp.where(cvalid[:, None], cand_u, SENTINEL)
            keys, cand, pack_ovf = keys_of(cand_u, cvalid)
            if not need_edges:
                return cand, keys, pack_ovf, None
            explore = jnp.ones(cand_u.shape[0], bool)
            for nm, f in con_fns:
                explore = explore & jax.vmap(f)(cand_u)
            return cand, keys, pack_ovf, explore

        combine = obs.prof_wrap("bfs.hstep_combine", combine)
        unpack_j = obs.prof_wrap("bfs.unpack",
                                 jax.jit(plan.unpack_rows))

        def hstep(frontier_p, fcount):
            fvalid = np.arange(FC) < int(fcount)
            if not acts:
                # hybrid with every arm demoted: the device only hashes
                z = np.zeros(0, bool)
                out = dict(cand=jnp.zeros((0, PW), jnp.int32),
                           cvalid=jnp.asarray(z),
                           keys=jnp.zeros((0, self.K), jnp.int32),
                           gen=0, dead=jnp.asarray(fvalid),
                           assert_bad=jnp.zeros((0, FC), bool),
                           overflow=0, deferred_preds=True)
                if need_edges:
                    out["explore"] = jnp.asarray(z)
                return out
            frontier = unpack_j(frontier_p)
            # grouped dispatches SCATTER into original instance order
            # (independence regrouping may have permuted the arms; the
            # candidate stream must stay byte-identical)
            jits, inst_blocks = self._hstep_groups(fused_max)
            en = np.empty((A, FC), bool)
            aok = np.empty((A, FC), bool)
            ov = np.empty((A, FC), np.int32)
            succ_all = np.empty((A, FC, W), np.int32)
            for jf, ii in zip(jits, inst_blocks):
                en_g, aok_g, ov_g, succ_g = jf(frontier)  # [a_g, FC(,W)]
                en[ii] = np.asarray(en_g)
                aok[ii] = np.asarray(aok_g)
                ov[ii] = np.asarray(ov_g)
                succ_all[ii] = np.asarray(succ_g)
            valid = en & fvalid[None, :]
            assert_bad = (~aok) & fvalid[None, :]
            overflow = int(np.where(fvalid[None, :], ov, 0).max(
                initial=0))
            dead = fvalid & ~en.any(axis=0)
            gen = int(valid.sum())
            cand_u = succ_all.reshape(A * FC, W)
            cvalid = valid.reshape(A * FC)
            cand, keys, pack_ovf, explore = combine(
                jnp.asarray(cand_u), jnp.asarray(cvalid))
            if overflow == 0 and bool(pack_ovf):
                overflow = OV_PACK
            out = dict(cand=cand, cvalid=jnp.asarray(cvalid), keys=keys,
                       gen=gen, dead=jnp.asarray(dead),
                       assert_bad=jnp.asarray(assert_bad),
                       overflow=overflow, deferred_preds=True)
            if explore is not None:
                out["explore"] = explore
            return out

        self._hstep_cache[FC] = hstep
        return hstep

    def _arm_group_plan(self, fused_max: int) -> List[List[int]]:
        """Compiled-action index groups for the fused arm-group paths
        (bfs host_seen split + mesh grouped expand).  Default plan is
        the legacy contiguous first-fit; with the independence matrix
        (ISSUE 15, JAXMC_ANALYZE_INDEP=0 opts out) commuting arms
        cluster into the same dispatch and the plan with FEWER groups
        wins.  Callers restore provenance order at the merge, so any
        plan here is count/trace byte-identical."""
        from ..analyze.independence import (indep_enabled,
                                            independence_report,
                                            plan_arm_groups)
        weights = [max(1, ca.n_slots) for ca in self.compiled]
        commutes = None
        if indep_enabled() and self.arms:
            try:
                irep = independence_report(self.model, self.arms)
                commutes = irep.commutes
                obs.current().gauge("analyze.independence_pairs",
                                    irep.commuting_pairs())
                obs.current().gauge("analyze.independence_safe",
                                    len(irep.por_safe))
            except Exception:
                if os.environ.get("JAXMC_DEBUG"):
                    raise
                commutes = None
        groups = plan_arm_groups(weights, list(self._ca_arm), commutes,
                                 fused_max)
        flat = [i for g in groups for i in g]
        obs.current().gauge("expand.regrouped",
                            int(flat != list(range(len(weights)))))
        return groups

    def _group_inst_blocks(self, groups: List[List[int]]
                           ) -> List[np.ndarray]:
        """Per-group FLAT INSTANCE indices (into the [A, ...] expansion
        axis) — the scatter targets that restore original provenance
        order after grouped dispatches."""
        w = [max(1, ca.n_slots) for ca in self.compiled]
        off = np.concatenate([[0], np.cumsum(w)]).astype(np.int64)
        return [np.concatenate([np.arange(off[i], off[i] + w[i])
                                for i in g]).astype(np.int64)
                for g in groups]

    def _hstep_groups(self, fused_max: int):
        """The arm-group fused expansion jits for the many-instance
        host_seen path: groups of compiled actions, each holding at
        most `fused_max` kernel INSTANCES (a single action whose slot
        fan-out alone exceeds the cap gets its own group — the cap
        bounds the fused-compile blowup, and one slotted kernel is a
        single program regardless of its slot count).  One jit per
        group.  Returns (jits, inst_blocks): inst_blocks[g] holds the
        original flat instance indices of group g's output rows, and
        the caller SCATTERS them back, so the candidate stream is
        identical to the per-action and fully-fused paths even when
        independence-driven regrouping reordered the arms."""
        cached = self._hstep_group_jits.get(fused_max)
        if cached is not None:
            obs.current().counter("compile.cache_hits")
            return cached
        obs.current().counter("compile.cache_misses")
        plan = self._arm_group_plan(fused_max)
        groups = [[self.compiled[i] for i in g] for g in plan]
        inst_blocks = self._group_inst_blocks(plan)

        def _mk(subset):
            def gexpand(frontier):
                ens, aoks, ovs, succs = [], [], [], []
                for ca in subset:
                    if ca.n_slots:
                        slots = jnp.arange(ca.n_slots, dtype=jnp.int32)
                        en, aok, ov, succ = jax.vmap(
                            jax.vmap(ca.fn, in_axes=(0, None)),
                            in_axes=(None, 0))(frontier, slots)
                        for si in range(ca.n_slots):
                            ens.append(en[si])
                            aoks.append(aok[si])
                            ovs.append(ov[si])
                            succs.append(succ[si])
                    else:
                        en, aok, ov, succ = jax.vmap(ca.fn)(frontier)
                        ens.append(en)
                        aoks.append(aok)
                        ovs.append(ov)
                        succs.append(succ)
                return (jnp.stack(ens), jnp.stack(aoks),
                        jnp.stack(ovs), jnp.stack(succs))

            return obs.prof_wrap("bfs.hstep_group", jax.jit(gexpand))

        jits = [_mk(g) for g in groups]
        obs.current().gauge("expand.fused_groups", len(jits))
        out = (jits, inst_blocks)
        self._hstep_group_jits[fused_max] = out
        return out

    def _check_new_rows(self, rows_np, skip_cons=False):
        """Compiled invariant (+ constraint unless skip_cons — the edge
        stream already computed per-candidate explore) checks over a
        batch of NEW (packed) rows (split host_seen mode defers them
        from the candidate stream). Pads to a power-of-two bucket (jit
        per bucket, cached) by repeating the first row so the padding is
        always a benign valid encoding."""
        n = len(rows_np)
        if n == 0:
            return np.zeros(0, bool), np.zeros(0, bool)
        cap = _pow2_at_least(n, lo=64)
        ckey = (cap, skip_cons)
        jf = self._newcheck_cache.get(ckey)
        if jf is not None:
            obs.current().counter("compile.cache_hits")
        else:
            obs.current().counter("compile.cache_misses")
            inv_fns = self.inv_fns
            con_fns = [] if skip_cons else self.constraint_fns
            plan = self.plan
            install = self._install_const_lanes

            @jax.jit
            def chk(rows_p, cvec):
                install(cvec)
                rows = plan.unpack_rows(rows_p)
                ok = jnp.ones(rows.shape[0], bool)
                for nm, f in inv_fns:
                    ok = ok & jax.vmap(f)(rows)
                ex_ = jnp.ones(rows.shape[0], bool)
                for nm, f in con_fns:
                    ex_ = ex_ & jax.vmap(f)(rows)
                self.kc.const_lanes = {}  # trace hygiene (see core)
                return ok, ex_

            self._newcheck_cache[ckey] = jf = obs.prof_wrap(
                "bfs.newcheck", chk, key=ckey)
        buf = np.repeat(rows_np[:1], cap, axis=0)
        buf[:n] = rows_np
        # the shared trace lock serializes first-call tracing of the
        # (donor-shared) jit against concurrent member threads: two
        # traces installing const lanes into the ONE shared KernelCtx
        # would cross-contaminate (unreachable in the fused batch path,
        # which never defers predicate checks — belt and braces)
        with self._trace_lock:
            ok, ex_ = jf(jnp.asarray(buf), self._cvec_jnp())
        return np.asarray(ok)[:n], np.asarray(ex_)[:n]

    # ---- resident mode: the whole BFS inside one jitted while_loop ----
    #
    # The seen-set (fingerprint keys), the frontier, and the level loop
    # itself are all device-resident inside lax.while_loop, so no chunk
    # or level waits on the host; the
    # host sees one small summary vector per MAXLVL-level batch. Capacity
    # overflows roll back to the last completed level (the carry keeps the
    # pre-level state) and report a grow-and-redo status, so counts stay
    # exact across regrowth.

    def _get_resident_run(self, SC, FCap, AccCap, VC, CH, LogCap=0,
                          LV=0):
        # maxlvl (levels per dispatch) is a TRACED argument, not part of
        # the compile key: the host adapts it to measured dispatch wall
        # time (so --checkpoint/--progress-every fire at useful
        # intervals, advisor r2) without recompiling.  LogCap > 0 is
        # the program that keeps the state log (ISSUE 44): a key of its
        # own (with LV, the levels a dispatch may run, which sizes its
        # row counts), the untraced program's stays what it was
        key = (SC, FCap, AccCap, VC, CH) + ((LogCap, LV) if LogCap else ())
        if key in self._res_cache:
            obs.current().counter("compile.cache_hits")
            return self._res_cache[key]
        obs.current().counter("compile.cache_misses")
        # this engine has none: the process may (ISSUE 37) — an earlier
        # engine with this program signature made the same program
        jitted = self._held_program(
            "bfs.resident_run", key,
            lambda: self._make_resident_run(*key))
        self._res_cache[key] = jitted
        return jitted

    def _make_resident_run(self, SC, FCap, AccCap, VC, CH, LogCap=0,
                           LV=0):
        key = (SC, FCap, AccCap, VC, CH) + ((LogCap, LV) if LogCap else ())
        A, W, K, PW = self.A, self.W, self.K, self.PW
        plan = self.plan
        C = A * CH
        inv_fns = self.inv_fns
        con_fns = self.constraint_fns
        keys_of = self._keys_fn()
        expand = self._expand_fn()
        check_deadlock = self.model.check_deadlock
        assert FCap % CH == 0
        # device POR (ISSUE 18): the persistent-set filter probes the
        # PRE-LEVEL seen snapshot (chunk bodies close over level()'s
        # `seen` — the merge runs after all chunks), so the resident,
        # level and mesh engines make identical ample decisions and
        # produce identical reduced counts.  The three counters always
        # ride the carry/summary (zero when POR is off) so the host
        # unpack is unconditional.
        por_plan = self._por_plan() if self.por else None
        por = por_plan is not None
        if por:
            por_inst = jnp.asarray(por_plan["inst_arm"])
            por_safe_v = jnp.asarray(por_plan["arm_safe"])
        # the merge's probe searches windows of a table this large, and
        # the program then counts the blocks that did (ISSUE 45); its
        # build reads windows of the new keys of a level this large, and
        # the program counts the blocks their compaction ran (ISSUE 48)
        windowed = _probe_window_rows(SC) < SC
        keyed = _build_form(AccCap) == "window"
        n_opt = keyed + windowed
        RB = _compact_block_rows(AccCap, FCap)

        def level(seen, seen_count, frontier, fcount):
            # frontier is PACKED [FCap, PW]; each chunk unpacks to lanes
            # right before expansion — the carry (and HBM residency) stay
            # at the packed width
            nchunks = (fcount + CH - 1) // CH

            def chunk_body(carry):
                (ci, acc_keys, acc_rows, acc_n, gen, stat,
                 bad_row, ovcode, pora, porx, porm) = carry
                with jax.named_scope("jaxmc.expand"):
                    base = ci * CH
                    chunk_p = lax.dynamic_slice(frontier, (base, 0),
                                                (CH, PW))
                    chunk = plan.unpack_rows(chunk_p)
                    fvalid = (jnp.arange(CH) + base) < fcount
                    en, aok, ov, succ = expand(chunk)
                    valid = en & fvalid[None, :]
                    gen = gen + jnp.sum(valid, dtype=jnp.int32)

                    # lane-capacity overflow inside an enabled action: abort.
                    # The max OV_* CODE rides along so the host can tell a
                    # compile-recovery demotion (OV_DEMOTED — raise no caps,
                    # run host_seen) from a real capacity overflow
                    ov_codes = jnp.where(fvalid[None, :], ov, 0)
                    ovf_lanes = jnp.any(ov_codes != 0)
                    ovcode = jnp.maximum(ovcode,
                                         jnp.max(ov_codes).astype(jnp.int32))
                    # Assert(FALSE) inside an enabled action
                    abad = (~aok) & fvalid[None, :]
                    assert_any = jnp.any(abad)
                    a_f = jnp.argmax(abad.reshape(-1)) % CH
                    # deadlock: a frontier state with no enabled action at all
                    dead = fvalid & ~jnp.any(en, axis=0)
                    dead_any = check_deadlock & jnp.any(dead)
                    d_f = jnp.argmax(dead)

                with jax.named_scope("jaxmc.compact"):
                    cand = succ.reshape(C, W)
                    cvalid = valid.reshape(C)
                    vcnt = jnp.sum(cvalid, dtype=jnp.int32)
                    # compact valid candidates to a VC-bounded block before
                    # hashing: ~95% of the dense (state x action) grid is
                    # disabled, so hashing only the survivors is the win
                    ops = ((1 - cvalid.astype(jnp.int32)),
                           jnp.arange(C, dtype=jnp.int32))
                    comp = lax.sort(ops, num_keys=1, is_stable=True)
                    cidx = comp[1][:VC]
                    rows_cu = jnp.take(cand, jnp.clip(cidx, 0, C - 1),
                                       axis=0)
                    vmask = jnp.arange(VC) < vcnt
                    rows_cu = jnp.where(vmask[:, None], rows_cu, SENTINEL)
                keys_c, rows_c, pack_ovf = keys_of(rows_cu, vmask)
                # pack-guard overflow aborts exactly like a lane
                # overflow (OV_PACK: the host names JAXMC_PACK=0);
                # kernel codes (esp. OV_DEMOTED) keep priority so the
                # hybrid demote-restart advice survives
                ovf_lanes = ovf_lanes | pack_ovf
                ovcode = jnp.where(
                    ovcode == 0,
                    jnp.where(pack_ovf, OV_PACK, 0).astype(jnp.int32),
                    ovcode)

                if por:
                    with jax.named_scope("jaxmc.expand"):
                        # persistent-set filter (ISSUE 18): probe the
                        # compacted candidate keys against the pre-level
                        # seen prefix, scatter the verdicts back onto the
                        # dense [A, CH] grid, mask every non-ample arm's
                        # candidates.  Deadlock/assert above read PRE-mask
                        # enabledness; gen drops to the reduced stream.
                        found_c, _ = _seen_probe(seen, seen_count, keys_c,
                                                 SC, vcnt)
                        found_g = jnp.zeros(C, dtype=bool).at[cidx].set(
                            found_c & vmask, mode="drop",
                            unique_indices=True)
                        keep_g, n_amp, n_exp = _por_mask(
                            found_g, cvalid, por_inst, por_safe_v, A, CH)
                        keep_c = jnp.take(keep_g, jnp.clip(cidx, 0, C - 1)) \
                            & vmask
                        n_masked = jnp.sum(vmask & ~keep_c,
                                           dtype=jnp.int32)
                        inv_key = jnp.concatenate([
                            jnp.ones((VC, 1), jnp.int32),
                            jnp.full((VC, K - 1), SENTINEL, jnp.int32)],
                            axis=1)
                        keys_c = jnp.where(keep_c[:, None], keys_c, inv_key)
                        rows_c = jnp.where(keep_c[:, None], rows_c, SENTINEL)
                        gen = gen - n_masked
                        pora = pora + n_amp
                        porx = porx + n_exp
                        porm = porm + n_masked

                with jax.named_scope("jaxmc.compact"):
                    # append the block at acc_n (clamped; overflow redoes the
                    # level so clobbered rows never count)
                    off = jnp.clip(acc_n, 0, AccCap - VC)
                    acc_keys = lax.dynamic_update_slice(acc_keys, keys_c,
                                                        (off, 0))
                    acc_rows = lax.dynamic_update_slice(acc_rows, rows_c,
                                                        (off, 0))
                    acc_n = acc_n + vcnt

                stat = jnp.where(
                    stat != ST_CONTINUE, stat,
                    jnp.where(
                        ovf_lanes, ST_OVF_LANES,
                        jnp.where(
                            vcnt > VC, ST_OVF_VC,
                            jnp.where(acc_n + VC > AccCap, ST_OVF_ACC,
                                      ST_CONTINUE))))
                # stat is still CONTINUE iff no earlier chunk reported
                # anything, so this is the first detection
                first_bad = (stat == ST_CONTINUE) & \
                    (assert_any | dead_any)
                bad_f = jnp.where(assert_any, a_f, d_f)
                brow = lax.dynamic_slice(frontier,
                                         (base + bad_f.astype(jnp.int32), 0),
                                         (1, PW))[0]
                bad_row = jnp.where(first_bad, brow, bad_row)
                stat = jnp.where(
                    (stat == ST_CONTINUE) & assert_any, ST_ASSERT,
                    jnp.where((stat == ST_CONTINUE) & dead_any,
                              ST_DEADLOCK, stat))
                return (ci + 1, acc_keys, acc_rows, acc_n, gen, stat,
                        bad_row, ovcode, pora, porx, porm)

            def chunk_cond(carry):
                # stop at the FIRST non-continue status: carrying on after
                # an assert/deadlock would skip the accumulator-overflow
                # checks (they only arm while stat == CONTINUE) and let
                # clamped writes clobber earlier candidate blocks
                ci, _, _, _, _, stat, _, _, _, _, _ = carry
                return (ci < nchunks) & (stat == ST_CONTINUE)

            with jax.named_scope("jaxmc.compact"):
                acc_keys0 = jnp.full((AccCap, K), SENTINEL, jnp.int32)
                acc_rows0 = jnp.full((AccCap, PW), SENTINEL, jnp.int32)
                bad_row0 = jnp.full((PW,), SENTINEL, jnp.int32)
            (_, acc_keys, acc_rows, acc_n, gen, stat, bad_row,
             ovcode, pora, porx, porm) = \
                lax.while_loop(chunk_cond, chunk_body,
                               (jnp.int32(0), acc_keys0, acc_rows0,
                                jnp.int32(0), jnp.int32(0),
                                jnp.int32(ST_CONTINUE), bad_row0,
                                jnp.int32(0), jnp.int32(0),
                                jnp.int32(0), jnp.int32(0)))

            # conservative seen-capacity check BEFORE the merge: every
            # accumulated candidate could be new
            stat = jnp.where((stat == ST_CONTINUE) &
                             (seen_count + acc_n > SC), ST_OVF_SEEN, stat)

            # ---- merge-dedup the level's candidates against seen ----
            # The shared O(new) rank-merge core (_rank_merge, also the
            # level and mesh engines' merge): the candidate block is
            # sorted by chained STABLE single-key passes and the
            # seen-set is never re-sorted — new keys merge by rank
            # (vectorized binary searches over the blocks of AccCap/64
            # sorted keys that hold a valid row, rounds from seen_count
            # and from the sampled neighbours; then the rows of seen2
            # fetched by gather through an inverse index: the row
            # scatters this replaced were 68-77 % of this engine's
            # device time on the v5e; ledger, PR 24), so the sort work
            # is O(new), not O(seen), per level; only the blocks of the
            # table that hold a live row after the level are built.
            # Every chunk appended its block at acc_n, so the valid keys
            # are a prefix of the accumulator and the sort takes the
            # smallest rung of its ladder that holds it (ISSUE 42).
            # What comes back names the new rows in a prefix as well
            # (nk_sidx[0:new_count]), and the compaction below reads
            # that prefix and no slot past its last block (ISSUE 46).
            rm = _rank_merge(seen, seen_count, acc_keys, AccCap, SC, K,
                             n_prefix=jnp.minimum(acc_n, AccCap))
            new_count = rm["new_count"]
            seen2 = rm["seen2"]
            seen_count2 = rm["seen_count2"]

            # ---- the next frontier: the level's new rows ----
            # rm["nk_sidx"] names them FIRST and in key order, so the
            # rows that exist are its prefix [0, new_count): they are
            # gathered in blocks of RB bounded on that count
            # (_gather_prefix, ISSUE 46), not over every AccCap slot.
            if not con_fns:
                # no CONSTRAINT can deselect a row: the new rows ARE the
                # frontier, and the blocks write straight into it
                explore_count = new_count
                with jax.named_scope("jaxmc.compact"):
                    front_rows, cblocks = _gather_prefix(
                        acc_rows, rm["nk_sidx"], new_count, FCap, RB)
            else:
                with jax.named_scope("jaxmc.compact"):
                    new_rows, nblocks = _gather_prefix(
                        acc_rows, rm["nk_sidx"], new_count, AccCap, RB)
                with jax.named_scope("jaxmc.constraint"):
                    # constraints: violating states stay fingerprinted
                    # in seen2 but are discarded (not distinct / checked
                    # / explored).  new_rows are PACKED; the predicate
                    # kernels read lanes
                    new_rows_u = plan.unpack_rows(new_rows)
                    explore = jnp.arange(AccCap) < new_count
                    for nm, f in con_fns:
                        explore = explore & jax.vmap(f)(new_rows_u)
                    explore_count = jnp.sum(explore, dtype=jnp.int32)
                    # the rows a CONSTRAINT discards must leave the
                    # frontier: a stable sort names the kept ones first
                    # (over every AccCap slot, as the predicates ran:
                    # `search.slots_constrained`; the cell
                    # `desk-constraint-4p` prices it, ISSUE 51), and
                    # the blocks gather only those
                    idx4 = jnp.arange(AccCap, dtype=jnp.int32)
                    ops4 = ((1 - explore.astype(jnp.int32)), idx4)
                    comp4 = lax.sort(ops4, num_keys=1, is_stable=True)
                    front_rows, cblocks = _gather_prefix(
                        new_rows, comp4[1], explore_count, FCap, RB)
                    cblocks = cblocks + nblocks
            stat = jnp.where((stat == ST_CONTINUE) &
                             (explore_count > FCap), ST_OVF_FRONT, stat)
            frontvalid = jnp.arange(FCap) < explore_count

            with jax.named_scope("jaxmc.scan"):
                inv_bad_any = jnp.asarray(False)
                inv_bad_idx = jnp.asarray(0, jnp.int32)
                inv_bad_which = jnp.asarray(-1, jnp.int32)
                front_rows_u = plan.unpack_rows(front_rows) if inv_fns \
                    else front_rows
                for wi, (nm, f) in enumerate(inv_fns):
                    ok = jax.vmap(f)(front_rows_u)
                    bad = frontvalid & ~ok
                    any_ = jnp.any(bad)
                    idx = jnp.argmax(bad).astype(jnp.int32)
                    first = jnp.logical_and(any_, ~inv_bad_any)
                    inv_bad_idx = jnp.where(first, idx, inv_bad_idx)
                    inv_bad_which = jnp.where(first, wi, inv_bad_which)
                    inv_bad_any = inv_bad_any | any_
                inv_row = lax.dynamic_slice(front_rows, (inv_bad_idx, 0),
                                            (1, PW))[0]
            bad_row = jnp.where(inv_bad_any & (stat == ST_CONTINUE),
                                inv_row, bad_row)
            stat = jnp.where((stat == ST_CONTINUE) & inv_bad_any,
                             ST_INV, stat)

            return (seen2, seen_count2, front_rows, explore_count, gen,
                    explore_count, stat, inv_bad_which, bad_row, ovcode,
                    pora, porx, porm, rm["probe_blocks"],
                    rm["merge_blocks"],
                    rm["sort_slots"] // _sort_unit(AccCap), cblocks) + \
                ((rm["newkey_blocks"],) if keyed else ()) + \
                ((rm["window_blocks"],) if windowed else ())

        def run(seen, seen_count, frontier, fcount, distinct,
                gen_lo, gen_hi, depth, max_states, maxlvl, *logged):
            # `logged` (LogCap > 0 alone): the state log [LogCap + FCap,
            # PW] and the rows it holds.  Every level that the search
            # goes on from appends its new frontier — the rows level()
            # hands back — as one block at log_n; the FCap rows past
            # LogCap are there so that the block is never clamped into
            # rows written before.  The carry's last three are the log,
            # log_n and the rows each of this dispatch's levels added.
            def cond(carry):
                lvls, stat = carry[8], carry[9]
                return (stat == ST_CONTINUE) & (lvls < maxlvl)

            def body(carry):
                (seen, seen_count, frontier, fcount, distinct,
                 gen_lo, gen_hi, depth, lvls, stat, which, brow,
                 ovcode, pora, porx, porm, pblocks, mblocks,
                 sunits, cblocks) = carry[:20]
                lvl = level(seen, seen_count, frontier, fcount)
                (seen2, seen_count2, front2, fcount2, gen_l, kept,
                 lstat, lwhich, lbrow, lovcode, lpora, lporx,
                 lporm, lpblocks, lmblocks, lsunits,
                 lcblocks) = lvl[:17]
                ovf = (lstat == ST_OVF_SEEN) | (lstat == ST_OVF_FRONT) | \
                    (lstat == ST_OVF_ACC) | (lstat == ST_OVF_VC) | \
                    (lstat == ST_OVF_LANES)
                logged2 = ()
                if LogCap:
                    log, log_n, lvl_rows = carry[20:23]
                    with jax.named_scope("jaxmc.trace.log"):
                        # a level that ends the search in a verdict is
                        # not logged: the walk reads the levels BEFORE
                        # the bad row's
                        lstat = jnp.where(
                            (lstat == ST_CONTINUE) &
                            (log_n + fcount2 > LogCap), ST_OVF_LOG, lstat)
                        ovf = ovf | (lstat == ST_OVF_LOG)
                        keep = lstat == ST_CONTINUE
                        log2 = lax.dynamic_update_slice(
                            log, front2, (log_n, jnp.int32(0)))
                        added = jnp.where(keep, fcount2, 0)
                        logged2 = (log2, log_n + added,
                                   lvl_rows.at[lvls].set(added))
                # overflow rolls the whole level back (growable caps are
                # redone after growth; lane overflow aborts with the
                # last completed level's exact counts).  The select
                # over the table also holds the loop's carry to the
                # layout the table arrives in (PERF.md §6, PR 29:
                # without it XLA:TPU keeps a row-major copy padded to
                # 128 lanes, 537 MB for a 21 MB table, and relayouts it
                # every level)
                seen2 = jnp.where(ovf, seen, seen2)
                seen_count2 = jnp.where(ovf, seen_count, seen_count2)
                front2 = jnp.where(ovf, frontier, front2)
                fcount2 = jnp.where(ovf, fcount, fcount2)
                distinct2 = jnp.where(ovf, distinct, distinct + kept)
                lo = (gen_lo.astype(jnp.uint32) +
                      gen_l.astype(jnp.uint32))
                wrapped = lo < gen_lo.astype(jnp.uint32)
                gen_lo2 = jnp.where(ovf, gen_lo, lo.astype(jnp.int32))
                gen_hi2 = jnp.where(ovf, gen_hi,
                                    gen_hi + wrapped.astype(jnp.int32))
                # deadlock/assert states belong to the CURRENT frontier
                # (depth d), unlike invariant violations which live in
                # the newly found level (d+1) — don't advance depth for
                # them, matching the interp/level/host_seen backends
                keep_depth = ovf | (lstat == ST_DEADLOCK) | \
                    (lstat == ST_ASSERT)
                depth2 = jnp.where(keep_depth, depth, depth + 1)
                stat2 = jnp.where(
                    lstat != ST_CONTINUE, lstat,
                    jnp.where(fcount2 == 0, ST_DONE,
                              jnp.where((max_states > 0) &
                                        (distinct2 >= max_states),
                                        ST_TRUNC, ST_CONTINUE)))
                # POR counters roll back with the level: a redone level
                # must not count its ample decisions twice
                pora2 = jnp.where(ovf, pora, pora + lpora)
                porx2 = jnp.where(ovf, porx, porx + lporx)
                porm2 = jnp.where(ovf, porm, porm + lporm)
                return (seen2, seen_count2, front2, fcount2, distinct2,
                        gen_lo2, gen_hi2, depth2, lvls + 1, stat2,
                        jnp.where(lstat == ST_INV, lwhich, which), lbrow,
                        jnp.where(lstat == ST_OVF_LANES, lovcode,
                                  ovcode), pora2, porx2, porm2,
                        # work done, not work kept: a rolled-back level
                        # sorted its rung and searched, built and
                        # gathered its blocks too
                        pblocks + lpblocks, mblocks + lmblocks,
                        sunits + lsunits, cblocks + lcblocks) + \
                    logged2 + tuple(
                        had + ran for had, ran in
                        zip(carry[len(carry) - n_opt:], lvl[17:]))

            carry0 = (seen, seen_count, frontier, fcount, distinct,
                      gen_lo, gen_hi, depth, jnp.int32(0),
                      jnp.int32(ST_CONTINUE), jnp.int32(-1),
                      jnp.full((PW,), SENTINEL, jnp.int32),
                      jnp.int32(0), jnp.int32(0), jnp.int32(0),
                      jnp.int32(0), jnp.int32(0), jnp.int32(0),
                      jnp.int32(0), jnp.int32(0))
            if LogCap:
                carry0 += logged + (jnp.zeros((LV,), jnp.int32),)
            # the carry's last, where the program has them: the blocks
            # the new keys' compaction ran (ISSUE 48), then the query
            # blocks that searched a window of the table (ISSUE 45),
            # counted as pblocks is
            carry0 += (jnp.int32(0),) * n_opt
            out = lax.while_loop(cond, body, carry0)
            (seen, seen_count, frontier, fcount, distinct, gen_lo,
             gen_hi, depth, _, stat, which, brow, ovcode, pora, porx,
             porm, pblocks, mblocks, sunits, cblocks) = out[:20]
            # indices 0-8 are the PR-6 summary; 9-11 are the per-
            # dispatch POR counters (ISSUE 18; zero when POR is off);
            # 12 is the query blocks the merge's probe searched over
            # the dispatch's levels (ISSUE 27: search.slots_probed), 13
            # the blocks of seen2 it built (ISSUE 29:
            # search.slots_merged), 14 the slots its key sorts were
            # given, in units of _sort_unit(AccCap) (ISSUE 42:
            # search.slots_sorted), 15 the blocks of RB rows the
            # compaction's gathers ran (ISSUE 46: search.slots_compacted)
            summary = jnp.stack([stat, seen_count, fcount, distinct,
                                 gen_lo, gen_hi, depth, which, ovcode,
                                 pora, porx, porm, pblocks, mblocks,
                                 sunits, cblocks])
            if LogCap:
                # ... and, where the log is kept, 16 is the rows it
                # holds and 17.. the rows each level of the dispatch
                # added (the host keeps the levels' offsets from them):
                # one block, one fetch
                log, log_n, lvl_rows = out[20:23]
                summary = jnp.concatenate([summary, log_n[None],
                                           lvl_rows])
            if n_opt:
                # ... and, where the probe has a window, the summary's
                # LAST word is the blocks that searched it (ISSUE 45:
                # search.slots_windowed); where the build has one, the
                # word before that (the last, without the probe's) is
                # the blocks the new keys' compaction ran (ISSUE 48:
                # search.slots_keyed); a program without them keeps the
                # summary it had
                summary = jnp.concatenate(
                    [summary] + [w[None] for w in out[len(out) - n_opt:]])
            if LogCap:
                return seen, frontier, summary, brow, log
            return seen, frontier, summary, brow

        # DONATED dispatch (ISSUE 6): the seen table (arg 0) and the
        # packed frontier (arg 2) — the two big device buffers — update
        # in place across dispatches instead of copying per batch (the
        # state log, arg 10, with them)
        donate = ((0, 2, 10) if LogCap else (0, 2)) if self.donate else ()
        return obs.prof_wrap("bfs.resident_run", jax.jit(
            run, static_argnames=(), donate_argnums=donate), key=key)

    def _get_trace_walk(self, LP, WCH, LMAX):
        key = (LP, WCH, LMAX)
        walk = self._walk_cache.get(key)
        if walk is None:
            walk = self._walk_cache[key] = self._held_program(
                "bfs.trace_walk", key,
                lambda: self._make_trace_walk(*key))
        return walk

    def _make_trace_walk(self, LP, WCH, LMAX):
        """The backward walk over the resident engine's state log
        (ISSUE 44), TLC's own way to an error trace: nothing of a path
        is kept during the search, it is generated again.  `log`
        [LP, PW] holds levels 0..depth-1 one after another, level l at
        offs[l]:offs[l+1]; `target` is the bad row, at level `depth`.
        For l = depth-1 .. 0 the logged level is expanded again in
        chunks of WCH rows with the search's own compiled arms, every
        successor is packed and compared with the target word for word
        (the stored form: under SYMMETRY or a VIEW the key is of the
        canonical row or the view, the stored row the state itself),
        and the lowest (slot, action) that matches becomes the target.
        A level stops at the first chunk that holds a match.  One block
        comes back, [LMAX + 2, PW + 1]: row l the state at level l with
        the action that led TO it in the last column (-1 at level 0),
        and in the last row the log rows expanded and whether every
        level found its parent."""
        A, W, PW = self.A, self.W, self.PW
        plan = self.plan
        C = A * WCH
        expand = self._expand_fn(scope="jaxmc.trace.walk")

        @jax.named_scope("jaxmc.trace.walk")
        def walk(log, offs, depth, target):
            def chunk_cond(c):
                ci, found = c[0], c[1]
                return (ci < c[4]) & ~found

            def chunk_body(c):
                ci, _, prow, act, nchunks, start, n, tgt = c
                base = ci * WCH
                chunk_p = lax.dynamic_slice(log, (start + base, 0),
                                            (WCH, PW))
                fvalid = (jnp.arange(WCH) + base) < n
                en, _, _, succ = expand(plan.unpack_rows(chunk_p))
                packed, _ = plan.pack_rows(succ.reshape(C, W))
                hit = jnp.all(packed == tgt[None, :], axis=1) \
                    .reshape(A, WCH) & en & fvalid[None, :]
                flat = hit.T.reshape(-1)       # slot-major: lowest slot,
                j = jnp.argmax(flat).astype(jnp.int32)    # then action
                found = jnp.any(flat)
                row = lax.dynamic_slice(chunk_p, (j // A, 0), (1, PW))[0]
                return (ci + 1, found, jnp.where(found, row, prow),
                        jnp.where(found, j % A, act), nchunks, start, n,
                        tgt)

            def level_cond(c):
                return (c[0] >= 0) & c[4]

            def level_body(c):
                lvl, tgt, block, nexp, _ = c
                start = offs[lvl]
                n = offs[lvl + 1] - start
                ci, found, prow, act, *_ = lax.while_loop(
                    chunk_cond, chunk_body,
                    (jnp.int32(0), jnp.asarray(False), tgt,
                     jnp.int32(-1), (n + WCH - 1) // WCH, start, n, tgt))
                block = lax.dynamic_update_slice(
                    block, prow[None, :], (lvl, 0))
                block = lax.dynamic_update_slice(
                    block, act[None, None], (lvl + 1, PW))
                return (lvl - 1, prow, block,
                        nexp + jnp.minimum(ci * WCH, n), found)

            block = jnp.full((LMAX + 2, PW + 1), -1, jnp.int32)
            block = lax.dynamic_update_slice(
                block, target[None, :], (depth, 0))
            _, _, block, nexp, ok = lax.while_loop(
                level_cond, level_body,
                (depth - 1, target, block, jnp.int32(0),
                 jnp.asarray(True)))
            tail = jnp.full((PW + 1,), -1, jnp.int32) \
                .at[0].set(nexp).at[1].set(ok.astype(jnp.int32))
            return lax.dynamic_update_slice(block, tail[None, :],
                                            (LMAX + 1, 0))

        return obs.prof_wrap("bfs.trace_walk", jax.jit(walk),
                             key=(LP, WCH, LMAX))

    def _count_logged(self, rows: int) -> None:
        """Rows the search appended to its state log, and their bytes."""
        tel = obs.current()
        tel.counter("search.log_rows", rows)
        tel.counter("search.log_bytes", 4 * self.PW * rows)

    def _walk_trace(self, log, lvl_off, depth: int, brow, FCap: int,
                    CH: int):
        """The counterexample behind `brow`, found at level `depth` of
        a resident search that kept its log: ONE dispatch (the walk),
        one fetch, then the decode — `_trace_to`'s form, a list of
        (state, label) from "Initial predicate" on.  None where a
        level held no parent of its target (never, on a log the search
        wrote: said loudly by the caller, not papered over)."""
        tel = obs.current()
        with tel.span("search.trace", depth=depth):
            with tel.span("trace.walk"):
                LMAX = _pow2_at_least(depth + 1, lo=8)
                offs = np.zeros(LMAX + 1, np.int32)
                offs[:depth + 1] = lvl_off[:depth + 1]
                walk = self._get_trace_walk(int(log.shape[0]),
                                            min(8 * CH, FCap), LMAX)
                block = np.asarray(walk(log, jnp.asarray(offs),
                                        jnp.int32(depth), brow))
            with tel.span("trace.decode"):
                nexp, ok = int(block[LMAX + 1, 0]), bool(block[LMAX + 1, 1])
                tel.counter("search.trace_rows_expanded", nexp)
                if not ok:
                    return None
                trace = [(self.layout.decode_packed(block[lvl, :self.PW]),
                          "Initial predicate" if lvl == 0 else
                          self.labels_flat[int(block[lvl, self.PW])])
                         for lvl in range(depth + 1)]
                tel.counter("search.trace_len", len(trace))
        return trace

    def _save_caps_profile(self, caps: Dict[str, int],
                           variant: str = "",
                           keys: Optional[Tuple[str, ...]] = None,
                           optional: Tuple[str, ...] = ()
                           ) -> None:
        """Persist the capacity profile a finished resident search ended
        with (ISSUE 6): the next resident run on this (module, layout)
        starts at these caps, so its warm-up compile covers the whole
        run and `window_recompiles` reads 0.  Best-effort: a profile is
        a hint, never allowed to fail a successful run.  `variant`/
        `keys` let engine families persist their own cap shapes (the
        mesh engine stores one profile per device count + exchange
        strategy, ISSUE 8)."""
        if not self.cap_profile:
            return
        try:
            from ..compile.cache import save_capacity_profile
            # profiles are NAMESPACED by backend platform (ISSUE 11):
            # the default (resident single-chip) variant is the
            # descriptor's namespace; engine families (mesh) pass their
            # own pre-namespaced variant + key shape
            kw = dict(chunk=int(self.chunk),
                      variant=self.backend_desc.profile_variant())
            if keys is not None:
                kw = dict(variant=variant, keys=keys, optional=optional)
            elif optional:
                kw["optional"] = optional
            path = save_capacity_profile(
                self.model.module.name, self._layout_sig(), dict(caps),
                **kw)
            if path:
                self.log(f"-- capacity profile saved to {path}")
        except Exception:  # noqa: BLE001 — hints never break runs
            pass

    def _pack_ovf_msg(self) -> str:
        return ("a value escaped its bit-packed lane's profiled range "
                "(compile/pack.py profiles raw-int lanes from sampled "
                "states with a margin): deepen --sample or rerun "
                "with JAXMC_PACK=0 (unpacked lanes) — counts stay exact "
                "either way")

    def _caps_note(self) -> str:
        """Which variable uses which bounded lane capacity — shown in
        capacity-overflow violations so the user knows WHAT to raise
        (the r3 MCraft_3s debugging pain: 'a container overflowed' with
        no name). Renders inside error paths — never allowed to raise."""
        try:
            return self._caps_note_inner()
        except Exception:  # noqa: BLE001 — diagnostics must not mask
            return "raise --seq-cap/--grow-cap/--kv-cap"

    def _caps_note_inner(self) -> str:
        parts: Dict[str, None] = {}  # ordered dedupe (fcn repeats keys)

        def walk(spec, path):
            k = spec.kind
            if k in ("seq", "growset", "kvtable"):
                flag = {"seq": "--seq-cap", "growset": "--grow-cap",
                        "kvtable": "--kv-cap"}[k]
                parts.setdefault(f"{path}:{k}[cap {spec.cap}, {flag}]")
            for sub in (spec.elems or ()):
                walk(sub, path)
            for sub in (spec.elem, spec.val):
                if sub is not None:
                    walk(sub, path)
            for _fields, fspecs in (spec.variants or ()):
                for sub in fspecs:
                    walk(sub, path)

        for v in self.layout.vars:
            walk(self.layout.specs[v], v)
        return "; ".join(parts) if parts else "no bounded containers"

    def _prepare_init(self, t0, warnings):
        """Shared init-state preparation for every device search mode:
        encode + dedup the enumerated init states, run the init-state
        invariant/refinement checks, log the TLC-format init line.

        Returns (init_rows, explored_init, n_init, err): err is a
        ready-to-return CheckResult when an initial state violates an
        invariant or a refinement's initial predicate, else None.

        The clean-path result is deterministic per engine, so it is
        memoized: repeated run() calls (bench warm-up + timed re-runs)
        skip the re-encode/canon/view work."""
        cached = getattr(self, "_init_prep", None)
        if cached is not None:
            return cached + (None,)
        layout = self.layout
        tel = obs.current()
        # the rows the layout build made of the initial states (ISSUE
        # 52), else they are encoded here: a follower of a cohort, or a
        # build that met a sample the layout refuses, whose encoding
        # raises here what it always raised
        raw = self._init_rows_built
        self._init_rows_built = None
        reused = raw is not None
        if not reused:
            raw = np.zeros((0, self.W), np.int32)
            if self.init_states:
                raw = np.stack([layout.encode(st)
                                for st in self.init_states])
        tel.gauge("layout.init_rows_reused", 1.0 if reused else 0.0)
        # TLC counts EVERY initial state as generated, also one whose
        # SYMMETRY orbit or VIEW value an earlier one already stored
        self._init_generated = len(raw)
        if len(raw) and self.canon_fn is not None:
            # cfg SYMMETRY: dedup/count init states by their orbit's
            # canonical representative, matching the interp's add_state
            # (which canonicalizes BEFORE the seen probe). Without this,
            # distinct init states sharing an orbit would inflate the
            # device counts and seed `seen` with duplicate canonical
            # fingerprints, breaking the sorted-unique invariant the
            # resident rank-merge relies on.
            raw = self._canon_host(raw)
        keyed = raw
        if len(raw) and self.view_fn is not None:
            # cfg VIEW: init states sharing a view value count ONCE
            # (TLC fingerprints the view) — keep the first state per key
            keyed = np.asarray(jax.vmap(self.view_fn)(jnp.asarray(raw)))
            if keyed.ndim == 1:
                keyed = keyed[:, None]
        # one row a key, the first that has it, in their order
        first: Dict[bytes, int] = {}
        for i, kk in enumerate(np.ascontiguousarray(keyed)):
            first.setdefault(kk.tobytes(), i)
        init_rows = raw[list(first.values())]
        n_init = len(init_rows)
        explored_init, init_viol = filter_init_states(self.model, layout,
                                                      init_rows)
        if init_viol is not None:
            nm, st = init_viol
            return init_rows, explored_init, n_init, self._mk_result(
                False, len(explored_init) + 1, n_init, 0, t0, warnings,
                Violation("invariant", nm, [(st, "Initial predicate")]))
        rv = self._refine_init(init_rows, explored_init)
        if rv is not None:
            nm, st = rv
            return init_rows, explored_init, n_init, self._mk_result(
                False, len(explored_init), n_init, 0, t0, warnings,
                Violation("property", nm, [(st, "Initial predicate")],
                          f"initial state violates {nm}'s initial "
                          f"predicate"))
        distinct = len(explored_init)
        self.log(f"Finished computing initial states: {distinct} distinct "
                 f"state{'s' if distinct != 1 else ''} generated.")
        self._init_prep = (init_rows, explored_init, n_init)
        if self._init_walks0 is not None:
            # walks of Init from this engine's entry to here: 1, or a
            # consumer has gone back to walking it for itself
            tel.gauge("layout.init_enumerations",
                      _init_walks(tel) - self._init_walks0)
        # the rows are what every later search reads: let the interpreter
        # states go.  A cfg whose Init is a function space keeps a
        # quarter of a million of them (1.3 M dicts and functions), and
        # every full collection of the garbage collector walks them all —
        # 0.4 s here, a search in fifty 1.3 s late on the chip (ISSUE 47)
        self.init_states = None
        return init_rows, explored_init, n_init, None

    # ---- checkpoint/resume (device backends) ----
    #
    # TLC checkpoints long runs to states/ (SURVEY.md §5, testout1:10);
    # the interp engine mirrors that with --checkpoint/--resume. The
    # device modes checkpoint BETWEEN levels (level and host_seen modes)
    # or between dispatches (resident mode), so a checkpoint is always a
    # consistent level boundary and resumed full-run counts stay exact.

    def _layout_sig(self) -> str:
        """Fingerprint of the lane encoding: a resume is only sound when
        the resuming process rebuilds the IDENTICAL layout (layout
        construction is deterministic for a given model + Bounds — BFS
        prefix sampling, no RNG)."""
        import hashlib
        lay = self.layout
        # the lane PLAN rides in the signature: checkpointed rows are
        # stored packed, so a resume must rebuild the identical packing
        # (it does: the plan derives deterministically from the same
        # sampling; JAXMC_PACK toggles change the signature on purpose)
        desc = repr((lay.vars, [lay.specs[v] for v in lay.vars],
                     [str(v) for v in lay.uni.values],
                     lay.plan.signature()))
        return hashlib.sha256(desc.encode()).hexdigest()

    def _program_sig(self) -> Optional[str]:
        """Signature of everything this engine's traced closures read
        (ISSUE 37): equal signatures => the same jitted programs, so
        an engine may dispatch the program an earlier engine of its
        process made (`compile/cache.py`'s registry) — a stamped or
        reformatted copy of a spec parses to the identical AST
        (`front/tla_ast.py` keeps no source positions) and pays a build
        and a search, not a trace, a lowering and a load.  Over the
        loaded model (`cache.model_canonical`: definitions with the
        cfg's constants bound, the checked formulas), the layout
        (`_layout_sig`), the engine's static shape, the backend, every
        JAXMC_* variable of the environment and the jax / jaxlib
        versions.  None — the engine keeps its own jits, as before the
        registry — where that cannot be said for certain: a hybrid
        engine (interpreter-fallback arms or predicates) or anything
        `cache.canonical` cannot render.  Made at the first ask, once
        (the mesh engine settles `fp_mode`, `K` and its backend
        descriptor after this class's constructor); `_demote_arms`
        forgets it."""
        if self._program_sig_memo is _SIG_UNSET:
            with obs.current().timed("compile.program_sig_s"):
                self._program_sig_memo = self._make_program_sig()
        return self._program_sig_memo

    def _make_program_sig(self) -> Optional[str]:
        import hashlib
        from ..compile import cache as _cache
        if self.fb_arms or self.fb_invs or self.fb_cons:
            return None
        try:
            import jaxlib
            por_plan = self._por_plan() if self.por else None
            desc = (
                "jaxmc.program/1", type(self).__name__,
                _cache.model_canonical(self.model), self._layout_sig(),
                _cache.canonical((
                    self.A, self.W, self.PW, self.K, self.key_width,
                    self.view_width, self.fp_mode, self.labels_flat,
                    [ca.n_slots for ca in self.compiled], self._ca_arm,
                    self._demotable, self.chunk, self.resident,
                    self.host_seen, self.store_trace, self.collect_edges,
                    self.por, None if por_plan is None else sorted(
                        (k, np.asarray(v).tolist())
                        for k, v in por_plan.items()),
                    self.donate, self.seen_cap is not None,
                    self.seen_mode_req, self._lift_names,
                    self.canon_fn is not None, self.sym_form,
                    self.sym_identity,
                    self._sym_fallback, self.sample_cfg,
                    sorted(vars(self.bounds).items()),
                    getattr(self, "D", None),
                    # this module's tuning constants, which a trace
                    # reads too (tests patch them)
                    FP_THRESHOLD, _PROBE_BLOCK_MIN, _PROBE_SAMPLE,
                    _MERGE_BLOCK_ROWS, _PROBE_WINDOW_ROWS,
                    _BUILD_WHOLE_KEYS)),
                (self.backend_desc.platform,
                 jax.devices()[0].device_kind,
                 self.backend_desc.profile_ns,
                 self.backend_desc.device_count),
                tuple(sorted((k, v) for k, v in os.environ.items()
                             if k.startswith("JAXMC_"))),
                jax.__version__, jaxlib.__version__)
        except (_cache.Unrenderable, RecursionError):
            return None
        return hashlib.sha256(repr(desc).encode()).hexdigest()

    def _held_program(self, site: str, key, make: Callable) -> Callable:
        """This engine's program of `site` for `key`: the one its
        process holds under `_program_sig()`, else `make()`'s."""
        from ..compile.cache import held_program
        return held_program(site, self._program_sig(), key, make)

    def _write_ck(self, mode: str, **state) -> None:
        # checksummed + schema-versioned container (engine/ckpt.py):
        # resume refuses truncated/corrupt/mismatched files with a
        # one-line CkptError instead of unpickling garbage
        from ..engine import ckpt as _ckpt
        payload = dict(mode=mode, module=self.model.module.name,
                       vars=list(self.model.vars),
                       layout_sig=self._layout_sig(),
                       key_fn=KEY_FN if self.fp_mode else None, **state)
        if self._tiers is not None and self._tiers.active:
            # the FULL tier hierarchy rides every checkpoint (ISSUE 12):
            # kill/resume mid-spill restores host and disk runs, so the
            # resumed dedup set is exactly the crashed run's
            payload["tiers"] = self._tiers.dump()
        try:
            with obs.current().span("checkpoint.write",
                                    mode=mode) as sp:
                # the size of the file it wrote, after the rename
                sp.attrs["bytes"] = n = _ckpt.write_checkpoint(
                    self.checkpoint_path, "device",
                    {"module": self.model.module.name, "mode": mode},
                    payload)
                obs.current().counter("checkpoint.bytes", n)
        except _ckpt.CkptError as ex:
            # a failed periodic write must not kill the search: keep
            # running on the previous checkpoint
            obs.current().counter("checkpoint.write_failures")
            self.log(f"WARNING: checkpoint write failed ({ex}); the run "
                     f"continues on the previous checkpoint")
            return
        self.log(f"Checkpointing run to {self.checkpoint_path}")

    def _load_ck(self, mode: str) -> dict:
        from ..engine.ckpt import CkptError, load_checkpoint
        _, ck = load_checkpoint(self.resume_from, kind="device")
        if ck.get("module") != self.model.module.name or \
                ck.get("vars") != list(self.model.vars):
            raise CkptError(
                f"cannot resume: checkpoint is for module "
                f"{ck.get('module')!r} with variables {ck.get('vars')}, "
                f"not {self.model.module.name!r}")
        if ck.get("mode") != mode:
            raise CkptError(
                f"cannot resume: checkpoint was written by the "
                f"{ck.get('mode')!r} device mode, this run uses {mode!r} "
                f"(re-run with the matching flags)")
        if ck.get("layout_sig") != self._layout_sig():
            raise CkptError(
                "cannot resume: the lane layout differs from the "
                "checkpoint's (different --seq-cap/--grow-cap/--kv-cap "
                "or a changed model?)")
        if self.fp_mode and ck.get("key_fn") != KEY_FN:
            raise CkptError(
                "cannot resume: the checkpoint's dedup keys were made by "
                "another fingerprint function (an older jaxmc); run the "
                "search from the start")
        if ck.get("tiers") is not None:
            # restore the cold tiers BEFORE any step compiles, so the
            # resumed engine probes (and its steps stream keys) from
            # the first level on
            self._ensure_tiers().load(ck["tiers"])
        return ck

    def _restore_ck_state(self, ck, graph):
        """Shared level/host_seen resume restore: validates trace and
        behavior-graph compatibility with THIS run's needs, then returns
        (distinct, generated, depth, trace_levels, frontier_maps, graph,
        frontier_sids) — the trace pair is None when store_trace is
        off."""
        if self.store_trace and ck.get("trace_levels") is None:
            raise ValueError(
                "cannot resume with traces: the checkpoint was written "
                "with --no-trace")
        frontier_sids = None
        if graph is not None:
            ckg = ck.get("graph")
            if ckg is None:
                raise ValueError(
                    "cannot resume with temporal properties: the "
                    "checkpoint has no behavior graph")
            if graph.collect_edges and not ckg.collect_edges:
                # mirror engine/explore.py's interp-resume guard: an
                # edge log cannot be reconstructed after the fact
                raise ValueError(
                    "cannot resume with this PROPERTY set: the "
                    "checkpoint's behavior graph has no edge log (it "
                    "was written for 'always'-form obligations only)")
            graph = ckg
            frontier_sids = ck["frontier_sids"]
        trace_levels = ck["trace_levels"] if self.store_trace else None
        frontier_maps = ck["frontier_maps"] if self.store_trace else None
        self.log(f"Resumed from {self.resume_from}: {ck['distinct']} "
                 f"distinct states, {len(ck['frontier'])} on queue.")
        return (ck["distinct"], ck["generated"], ck["depth"],
                trace_levels, frontier_maps, graph, frontier_sids)

    def _ck_state_kwargs(self, distinct, generated, depth, trace_levels,
                         frontier_maps, graph, frontier_sids):
        """Shared level/host_seen checkpoint payload fields."""
        return dict(
            distinct=distinct, generated=generated, depth=depth,
            trace_levels=trace_levels if self.store_trace else None,
            frontier_maps=frontier_maps if self.store_trace else None,
            graph=graph, frontier_sids=frontier_sids)

    def _write_host_snapshot(self, trace_levels, frontier_maps, graph,
                             depth, generated) -> None:
        """Demotion snapshot: an INTERP-format checkpoint (engine/ckpt.py
        payload, `<checkpoint>.host`) rebuilt from the host-side trace
        levels, so when the device path dies terminally the parallel CPU
        engine resumes from the last level barrier instead of restarting
        from scratch (cli.py owns the fallback).

        Exactness: every kept state of every level is decoded and
        re-fingerprinted with the interp's own state_fingerprint, so the
        resumed dedup set is exact.  Constraint-DISCARDED fingerprints
        are not reconstructible from rows the device never kept — their
        absence is count-equivalent: the resumed engine re-generates and
        re-discards such a state on first contact, exactly what the
        serial engine counts.  Skipped (with one log line) when traces
        are off (--no-trace), in resident mode (no host rows), or when
        cfg SYMMETRY ran UNREDUCED on the device (the interp would
        reduce, so the carried counts would not be comparable)."""
        if not self.store_trace or not self.checkpoint_path:
            return
        if self.model.symmetry is not None and self.canon_fn is None \
                and not self.sym_identity:
            # identity groups excepted: the interp reduces them to the
            # same (unreduced) partition, so the snapshot stays exact
            if not getattr(self, "_host_snap_skip_logged", False):
                self._host_snap_skip_logged = True
                self.log("-- no host snapshot: SYMMETRY ran unreduced on "
                         "the device (interp counts would differ)")
            return
        from ..engine import ckpt as _ckpt
        from ..engine.explore import make_canonicalizer, state_fingerprint
        model = self.model
        vars = model.vars
        canon = make_canonicalizer(model)
        view_expr = getattr(model, "view", None)  # None on device paths
        states: List[Dict[str, Any]] = []
        parents: List[Optional[int]] = []
        labels: List[str] = []
        depth_of: List[int] = []
        seen: Dict[Any, int] = {}
        level_sids: List[List[int]] = []
        for lvl, (rows, prov, par_div) in enumerate(trace_levels):
            sids: List[int] = []
            for ridx in frontier_maps[lvl]:
                ridx = int(ridx)
                st = self.layout.decode_packed(np.asarray(rows[ridx]))
                sid = len(states)
                if prov is None:
                    parents.append(None)
                    labels.append("Initial predicate")
                else:
                    p = int(prov[ridx])
                    a, pf = p // par_div, p % par_div
                    parents.append(level_sids[lvl - 1][pf])
                    labels.append(self.labels_flat[a])
                states.append(st)
                depth_of.append(lvl)
                key = state_fingerprint(model, canon, view_expr, vars, st)
                # an fp128 collision may have collapsed two interp-
                # distinct states device-side; keep the first sid — the
                # resumed run stays exact going forward
                seen.setdefault(key, sid)
                sids.append(sid)
            level_sids.append(sids)
        collect_edges = graph is not None and graph.collect_edges
        payload = _ckpt.interp_payload(
            model, vars, states, parents, labels, depth_of,
            level_sids[-1] if level_sids else [], generated,
            max(depth - 1, 0), seen,
            graph.edges if collect_edges else None, collect_edges, [])
        snap = self.checkpoint_path + ".host"
        try:
            with obs.current().span("checkpoint.host_snapshot",
                                    states=len(states)):
                _ckpt.write_checkpoint(
                    snap, "interp",
                    {"module": model.module.name,
                     "engine": "device-snapshot"},
                    payload)
        except _ckpt.CkptError as ex:
            obs.current().counter("checkpoint.write_failures")
            self.log(f"WARNING: host snapshot write failed ({ex}); the "
                     f"run continues on the previous snapshot")
            return
        obs.current().counter("checkpoint.host_snapshots")
        self.log(f"Host snapshot (CPU-resumable) written to {snap}")

    def _run_resident(self) -> CheckResult:
        t0 = time.time()
        tel = obs.current()
        layout = self.layout
        W, K = self.W, self.K
        warnings = ["resident mode (W={}): dedup on 128-bit fingerprints; "
                    "collision probability < n^2 * 2^-129".format(W)]
        # the state log a counterexample is walked back over (ISSUE 44)
        # is kept unless the caller gave the trace up (store_trace
        # False: --no-trace) or something else takes it away, which is
        # said by name
        no_trace_why = None if self.store_trace else "--no-trace"
        if self.store_trace and self.resume_from:
            no_trace_why = "--resume"
            warnings.append(
                "resident mode: no counterexample trace, given up by "
                "--resume (a checkpoint carries no state log; rerun "
                "without --resume for a trace)")
        keep_log = no_trace_why is None
        warnings.extend(self._temporal_warnings())
        warnings.extend(self._symmetry_warnings())
        warnings.extend(self._por_warnings())

        with tel.span("search.init"):
            init_rows, explored_init, n_init, err = \
                self._prepare_init(t0, warnings)
        if err is not None:
            return err
        generated = self._init_generated
        distinct = len(explored_init)

        CH = _pow2_at_least(self.chunk, lo=64)
        # every overflow-growth costs a full XLA recompile (minutes on
        # the big while_loop program), while capacity is cheap device
        # memory (seen keys at SC=1<<20 are 20MB) - so on an accelerator
        # start generous; on CPU (tests) stay small to keep compiles fast
        on_accel = jax.devices()[0].platform != "cpu"
        if self._res_caps is not None:
            caps = dict(self._res_caps)
        elif self._res_caps_hint:
            # caller-supplied steady-state caps (the corpus manifest's
            # res_caps record or a persisted capacity profile) are the
            # BASE, not a floor merged into the platform defaults: a small model's hint
            # must be allowed to SHRINK the buckets (the capacity-sized
            # sorts/gathers inside the level step are exactly what made
            # the r04 kernel lose to the interpreter on small models).
            # A wrong hint only costs an overflow-growth recompile.
            h = self._res_caps_hint
            caps = {
                "SC": _pow2_at_least(int(h.get("SC", 1)), lo=256),
                "FCap": _pow2_at_least(int(h.get("FCap", 1)), lo=64),
                "AccCap": _pow2_at_least(int(h.get("AccCap", 1)),
                                         lo=128),
                "VC": _pow2_at_least(int(h.get("VC", 1)), lo=64)}
        else:
            caps = ({"SC": 1 << 20, "FCap": max(1 << 16, CH),
                     "AccCap": 1 << 17, "VC": 1 << 14} if on_accel else {
                "SC": _pow2_at_least(max(4 * n_init, 1), lo=1 << 15),
                "FCap": CH, "AccCap": 1 << 15, "VC": 1 << 13})
        # a device seen cap (ISSUE 12) bounds the hot tier from the
        # start: defaults/hints/profiles above it would keep the run
        # from ever spilling (the floors below may still soft-breach a
        # cap too small to seat the init keys)
        if self.seen_cap is not None:
            caps["SC"] = min(caps["SC"], self.seen_cap)
        # floors no hint may undercut: the seen table must seat every
        # init key and the frontier every init row (a 256-cap hint on a
        # 1600-init model would otherwise crash the seeding, not grow)
        caps["SC"] = max(caps["SC"],
                         _pow2_at_least(max(4 * n_init, 1), lo=256))
        if self.seen_cap is not None and caps["SC"] > self.seen_cap:
            self._note_cap_breach(caps["SC"], "the initial states' keys")
        caps["FCap"] = max(caps["FCap"], _pow2_at_least(max(n_init, 1),
                                                        lo=CH))
        # VC can never usefully exceed the dense candidate-grid size
        # A*CH (and must not: [:VC] slices of C-row arrays assume VC<=C);
        # AccCap must cover one VC block past acc_n, and is kept no
        # smaller than FCap: a level's new rows sit among its AccCap
        # candidates, so a frontier the accumulator could not fill
        # would be capacity nothing uses (the compaction's block,
        # _compact_block_rows, is cut to FCap either way)
        caps["VC"] = min(caps["VC"], self.A * CH)
        caps["AccCap"] = max(caps["AccCap"], 2 * caps["VC"], caps["FCap"])
        if keep_log:
            # the log's capacity in rows, a cap like the others (pinned,
            # hinted or learned; else it starts where SC starts), and it
            # must seat the initial frontier
            given = (self._res_caps or self._res_caps_hint or {}) \
                .get("LogCap")
            caps["LogCap"] = max(
                _pow2_at_least(int(given), lo=256) if given
                else (1 << 20 if on_accel else 1 << 15),
                _pow2_at_least(max(n_init, 1), lo=256))
        else:
            caps.pop("LogCap", None)
        # levels per dispatch: the host only sees status (and can only
        # checkpoint / log progress) between dispatches, so maxlvl adapts
        # to measured dispatch wall time — targeting the tighter of
        # progress_every/checkpoint_every — instead of a fixed 64 that
        # could run for hours on a large model (advisor r2)
        # start SMALL and double up: the first dispatches are the ones
        # with no timing evidence, and a 64-level opener on a big model
        # could run for hours before the host could checkpoint or log
        # progress (review r3) — a few extra cheap dispatches at the
        # start cost almost nothing
        # ...unless a PREVIOUS run on this engine already learned the
        # model's depth/dispatch timing: warm re-runs (bench timed
        # windows) then cover the whole search in as few dispatches as
        # the adaptive controller settled on, instead of re-ramping
        # 4 -> 8 -> 16 every run
        maxlvl = min(getattr(self, "_res_maxlvl_warm", 4),
                     self._res_maxlvl)
        target_s = max(1.0, min(
            self.progress_every or 30.0,
            (self.checkpoint_every or 1e9) if self.checkpoint_path
            else 1e9))

        # packed init boundary: keys + packed rows in one pass; a pack
        # overflow at init is an observation gap (abort exactly)
        # the host's pieces of the seed as seconds on the program's own
        # clock (ISSUE 34; bench/SPANS.records.md): float counters, not
        # spans — `seed.keys_s` the keys and their order, `seed.tables_s`
        # what the host builds of the tables (their HEADS, the init rows:
        # since ISSUE 35 no table), `seed.upload_s` the calls that hand
        # the device the heads and make its tables, up to their return
        with tel.span("search.seed"):
            with tel.timed("seed.keys_s"):
                init_keys, init_packed, init_povf = \
                    self._host_keys(init_rows)
            if init_povf:
                return self._mk_result(
                    False, distinct, generated, 0, t0, warnings,
                    Violation("error", "capacity overflow", [],
                              self._pack_ovf_msg()))
            with tel.timed("seed.keys_s"):
                order = np.lexsort(tuple(init_keys[:, i]
                                         for i in reversed(range(K))))
            with tel.timed("seed.tables_s"):
                fr_head = init_packed[explored_init]
                seen_head = init_keys[order]

        depth = 0
        if self.resume_from:
            ck = self._load_ck("resident")
            for kk in caps:
                caps[kk] = max(caps[kk], ck.get("caps", {}).get(kk, 0))
            # re-apply the cap invariants: the checkpointing run may have
            # used a different --chunk, and VC must never exceed A*CH
            caps["VC"] = min(caps["VC"], self.A * CH)
            caps["AccCap"] = max(caps["AccCap"], 2 * caps["VC"],
                                 caps["FCap"])
            # the checkpoint's rows are the heads
            seen_head, fr_head = ck["seen"], ck["frontier"]
            distinct = ck["distinct"]
            generated = ck["generated"]
            depth = ck["depth"]
            self.log(f"Resumed from {self.resume_from}: {distinct} "
                     f"distinct states, {len(fr_head)} on queue.")
            if not len(fr_head):
                # a COMPLETED-run checkpoint (final_checkpoint, the
                # serve daemon's warm-resume source): nothing left to
                # explore — replay the stored verdict with ZERO kernel
                # dispatches (and therefore zero window recompiles)
                self.log("Model checking completed. No error has been "
                         "found.")
                self.log(f"{generated} states generated, {distinct} "
                         f"distinct states found, 0 states left on "
                         f"queue.")
                self.log(f"The depth of the complete state graph search "
                         f"is {depth}.")
                if self.checkpoint_path and self.final_checkpoint and \
                        self.checkpoint_path != self.resume_from:
                    self._write_ck(
                        "resident", caps=dict(caps),
                        seen=np.asarray(seen_head),
                        frontier=np.zeros((0, self.PW), np.int32),
                        distinct=distinct, generated=generated,
                        depth=depth)
                return self._mk_result(True, distinct, generated,
                                       depth - 1, t0, warnings)
        fcount, seen_count = len(fr_head), len(seen_head)

        # both tables made on the device from their heads, capped or
        # not (`_seed_tables`), and the scalar operands' uploads
        with tel.span("search.seed"), tel.timed("seed.upload_s"):
            seen, frontier = self._seed_tables(
                caps["SC"], caps["FCap"], seen_head, fr_head)
            max_states = jnp.int32(self.max_states or 0)
            gen_lo = int(np.int32(np.uint32(generated & 0xFFFFFFFF)))
            gen_hi = generated >> 32
            state = (seen, jnp.int32(seen_count), frontier,
                     jnp.int32(fcount), jnp.int32(distinct),
                     jnp.int32(gen_lo), jnp.int32(gen_hi),
                     jnp.int32(depth))
            # the state log starts as level 0, the initial frontier;
            # lvl_off[l] is where level l begins in it
            log, lvl_off = None, [0, fcount]
            if keep_log:
                tel.counter("search.seed_bytes", fr_head.nbytes)
                log = self._device_table(
                    (caps["LogCap"] + caps["FCap"], self.PW), fr_head)
                self._count_logged(fcount)
        grow_flag = {ST_OVF_SEEN: "SC", ST_OVF_FRONT: "FCap",
                     ST_OVF_ACC: "AccCap", ST_OVF_VC: "VC",
                     ST_OVF_LOG: "LogCap"}
        # first progress line immediately (ISSUE 2): short runs get at
        # least one record; same format as the interval lines below
        self.log(f"Progress({depth}): {generated} states generated, "
                 f"{distinct} distinct states found, "
                 f"{fcount} states left on queue."
                 f"{obs.eta_suffix(distinct)}")
        last_progress = last_ck = time.time()
        redo_after_spill = False
        while True:
            # chaos sites: crash / device failure between dispatches
            # (the only host-attention points resident mode has)
            from .. import faults
            faults.kill_self("run_kill", level=depth, engine="resident")
            faults.inject("device_run_fail", level=depth)
            if self._drain_requested(warnings, "resident"):
                if self.checkpoint_path:
                    self._write_ck(
                        "resident", caps=dict(caps),
                        seen=np.asarray(seen[:seen_count]),
                        frontier=np.asarray(frontier[:fcount]),
                        distinct=distinct, generated=generated,
                        depth=depth)
                return self._mk_result(True, distinct, generated, depth,
                                       t0, warnings, None,
                                       truncated=True, drained=True)
            ck_key = (caps["SC"], caps["FCap"], caps["AccCap"],
                      caps["VC"], CH) + (
                (caps["LogCap"], self._res_maxlvl) if keep_log else ())
            new_here = ck_key not in self._res_cache
            runf = self._get_resident_run(*ck_key)
            # a program the process already holds (ISSUE 37) has its
            # executable: nothing compiles or loads, and the span, the
            # level record and what reads them (the watchdog,
            # `window_recompiles`) say so
            fresh_compile = new_here and not _has_executable(runf)
            # the program's capacity-sized tables at the capacities in
            # force: seen and frontier (handed in, handed back) and the
            # level accumulator's keys and rows (re-made every level at
            # AccCap and sorted whole); bench/SPANS.deep.md has the
            # other engines' definitions
            tel.gauge("merge.build_form", _build_form(caps["AccCap"]))
            tel.gauge("search.table_bytes", 4 * (
                caps["SC"] * K + caps["FCap"] * self.PW
                + caps["AccCap"] * (K + self.PW)
                + (int(log.shape[0]) * self.PW if keep_log else 0)))
            t_disp = time.time()
            # once the run has spilled (ISSUE 12), every level needs a
            # cold-tier probe at the host boundary: pin the dispatch to
            # ONE level so the host sees each committed frontier
            eff_maxlvl = 1 if (self._tiers is not None
                               and self._tiers.active) else maxlvl
            with tel.span("search.dispatch", maxlvl=eff_maxlvl,
                          fresh_compile=fresh_compile):
                if keep_log:
                    seen, frontier, summary, brow, log = runf(
                        *state, max_states, jnp.int32(eff_maxlvl), log,
                        jnp.int32(lvl_off[-1]))
                else:
                    seen, frontier, summary, brow = runf(
                        *state, max_states, jnp.int32(eff_maxlvl))
                jax.block_until_ready(summary)
            disp_wall = time.time() - t_disp
            # adapt levels-per-dispatch toward the host-attention target;
            # a dispatch that just paid an XLA recompile (cap growth) is
            # not evidence about execution speed — skip it (an engine's
            # first dispatch of a held program too: the schedule of
            # dispatches stays what it was before there was a registry)
            if new_here:
                pass
            elif disp_wall > 1.5 * target_s and maxlvl > 1:
                maxlvl = max(1, maxlvl // 2)
            elif disp_wall < target_s / 4 and \
                    maxlvl < self._res_maxlvl:
                maxlvl = min(self._res_maxlvl, maxlvl * 2)
            with tel.span("search.fetch"):
                summary = np.asarray(summary)
                # a program whose probe has a window (ISSUE 45) says
                # last how many of its query blocks searched it
                window_blocks = None
                if _probe_window_rows(caps["SC"]) < caps["SC"]:
                    window_blocks = int(summary[-1])
                    summary = summary[:-1]
                # ... and one whose build reads windows of the new keys
                # (ISSUE 48), before that, how many blocks their
                # compaction ran
                newkey_blocks = None
                if _build_form(caps["AccCap"]) == "window":
                    newkey_blocks = int(summary[-1])
                    summary = summary[:-1]
                fcount_in, gen_in, dist_in, depth_in, seen_in = \
                    fcount, generated, distinct, depth, seen_count
                stat = int(summary[0])
                seen_count = int(summary[1])
                fcount = int(summary[2])
                distinct = int(summary[3])
                generated = (int(np.uint32(summary[5])) << 32) | \
                    int(np.uint32(summary[4]))
                depth = int(summary[6])
                which = int(summary[7])
                ovcode = int(summary[8])
                # rows that entered the seen table and that a CONSTRAINT
                # kept out of the frontier (ISSUE 51): the host has both
                # terms, the carry need not grow.  Taken before the cold
                # tiers' filter below: a cold duplicate is no discard
                discarded = (seen_count - seen_in) - (distinct - dist_in)
                # per-dispatch POR deltas: run() zero-seeds them per
                # dispatch and rolls back overflowed levels, so summing
                # across dispatches (including redos) never double-counts
                self._por_stats["ample"] += int(summary[9])
                self._por_stats["expanded"] += int(summary[10])
                self._por_stats["masked"] += int(summary[11])
                probe_blocks = int(summary[12])
                merge_blocks = int(summary[13])
                sort_units = int(summary[14])
                compact_blocks = int(summary[15])
                if keep_log:
                    # a level that added no row ended the search, was
                    # rolled back or never ran
                    logged_in = lvl_off[-1]
                    for added in summary[17:]:
                        if added:
                            lvl_off.append(lvl_off[-1] + int(added))
                    assert lvl_off[-1] == int(summary[16])
                    self._count_logged(lvl_off[-1] - logged_in)
                # cold-tier filter (ISSUE 12): after a spill the device
                # table restarted empty, so a committed level's frontier
                # may hold rows whose keys live in the host/disk runs —
                # exactly the rows the uncapped table would have deduped.
                # Probe and drop them (order-preserving) before counts,
                # truncation decisions, or the next dispatch see them.
                # Rolled-back levels (grow statuses) keep their frontier —
                # it was already filtered when it was admitted.
                if self._tiers is not None and self._tiers.active and \
                        fcount > 0 and stat not in grow_flag and \
                        stat not in (ST_OVF_LANES, ST_DONE):
                    with tel.span("tier.pull", rows=fcount):
                        fr_np = np.asarray(frontier[:fcount])
                    keep = self._tier_keep_mask(fr_np)
                    n_dup = int((~keep).sum())
                    if n_dup:
                        with tel.span("tier.push", rows=fcount - n_dup):
                            kept_rows = np.ascontiguousarray(fr_np[keep])
                            distinct -= n_dup
                            fcount = len(kept_rows)
                            fr_full = np.full(
                                (int(frontier.shape[0]), self.PW),
                                SENTINEL, np.int32)
                            fr_full[:fcount] = kept_rows
                            frontier = jnp.asarray(fr_full)
                    if stat == ST_TRUNC and self.max_states and \
                            distinct < self.max_states:
                        stat = ST_CONTINUE  # phantom limit: dups un-counted
                    if fcount == 0 and stat == ST_CONTINUE:
                        stat = ST_DONE  # the whole level was cold dups
                    self._tiers.publish_gauges(seen_count)
                self._res_caps = dict(caps)
                # one record per DISPATCH (the host only sees level batches
                # in resident mode): `level` is the depth reached, so indices
                # stay monotone — equal across an overflow-redo dispatch.
                # frontier/generated/new keep the other paths' semantics:
                # frontier going IN, per-dispatch generated/new deltas (so
                # summing `generated` across records gives the run total)
                tel.level(depth, dispatch=True, frontier=fcount_in,
                          generated=generated - gen_in,
                          new=distinct - dist_in, distinct=distinct,
                          seen=seen_count, status=stat,
                          fresh_compile=fresh_compile,
                          wall_s=round(disp_wall, 6))
            # work against capacity: every level the dispatch ran (one
            # that ended in a rollback or a verdict ran too, and left
            # depth where it was) sorted the rung of AccCap's ladder
            # that held its candidates: the dispatch's loop carry
            # summed them
            lvls = depth - depth_in + (stat in grow_flag or stat in (
                ST_OVF_LANES, ST_DEADLOCK, ST_ASSERT))
            tel.counter("search.slots_sorted", sort_units
                        * _sort_unit(caps["AccCap"]))
            tel.counter("search.rows_valid", generated - gen_in)
            # ... and binary-searched only the query blocks that held a
            # valid key: the dispatch's loop carry counted them
            tel.counter("search.slots_probed", probe_blocks
                        * _probe_block_rows(caps["AccCap"]))
            if window_blocks is not None:
                tel.counter("search.slots_windowed", window_blocks
                            * _probe_block_rows(caps["AccCap"]))
            tel.counter("search.seen_slots", lvls * caps["SC"])
            # ... and built only the blocks of the table that held a
            # live row after each level: counted in the carry as well
            tel.counter("search.slots_merged", merge_blocks
                        * _merge_block_rows(caps["SC"]))
            if newkey_blocks is not None:
                tel.counter("search.slots_keyed", newkey_blocks
                            * _probe_block_rows(caps["AccCap"]))
            tel.counter("search.rows_new", distinct - dist_in)
            # ... and gathered the new rows into the next frontier in
            # blocks bounded by their count (ISSUE 46): counted there too
            tel.counter("search.slots_compacted", compact_blocks
                        * _compact_block_rows(caps["AccCap"],
                                              caps["FCap"]))
            if self.constraint_fns:
                # ... and judged every slot of the level's buffer of new
                # rows, and sorted as many, whatever held a row (ISSUE
                # 51): a rolled-back level too, as the sorts count theirs
                tel.counter("search.slots_constrained",
                            lvls * caps["AccCap"])
                tel.counter("search.rows_discarded", discarded)
            if redo_after_spill and generated > gen_in:
                # the level a spill rolled back has now run a second
                # time (one level a dispatch once tiers are active):
                # its candidates were expanded, sorted and merged twice
                tel.counter("tier.redone_rows", generated - gen_in)
                redo_after_spill = False
            self._fp_occupancy = seen_count

            if stat in grow_flag:
                what = grow_flag[stat]
                old = caps[what]
                if what == "SC" and self.seen_cap is not None and \
                        old >= self.seen_cap and seen_count > 0:
                    # device tier full (ISSUE 12): instead of growing
                    # past the cap, compact the sorted prefix out to
                    # the cold tiers, restart the device table empty,
                    # and redo the level (the rollback preserved the
                    # pre-level state); subsequent dispatches run one
                    # level at a time with a cold-tier probe each
                    seen = self._tier_spill(seen, seen_count)
                    seen_count = 0
                    redo_after_spill = True
                    self.log(f"-- tier: device seen cap "
                             f"{self.seen_cap} reached; spilled the "
                             f"device tier to "
                             f"host={self._tiers.host_keys}/"
                             f"disk={self._tiers.disk_keys} keys "
                             f"(level {depth} redone)")
                    state = (seen, jnp.int32(seen_count), frontier,
                             jnp.int32(fcount), jnp.int32(distinct),
                             jnp.int32(summary[4]),
                             jnp.int32(summary[5]), jnp.int32(depth))
                    continue
                # x4: each growth recompiles the whole program, so
                # over-shooting is much cheaper than growing twice
                caps[what] = old * 4
                if what == "VC":
                    caps[what] = min(caps[what], self.A * CH)
                if what == "SC" and self.seen_cap is not None:
                    if old < self.seen_cap:
                        # grow the device tier all the way TO the cap
                        # before spilling (the x4 overshoot must not
                        # spill at a fraction of the configured cap)
                        caps[what] = min(caps[what], self.seen_cap)
                    else:
                        # at/above the cap with nothing left to spill
                        # (the rolled-back table is empty): one
                        # level's new keys alone exceed the cap — grow
                        # past it, named, exactly like the level
                        # engine's soft breach (a clamp here would be
                        # zero growth: an infinite redo of the same
                        # dispatch)
                        self._note_cap_breach(
                            caps[what], "one level's candidates")
                if what == "SC":
                    pad = jnp.full((caps[what] - old, K), SENTINEL,
                                   jnp.int32)
                    seen = jnp.concatenate([seen, pad])
                elif what == "FCap":
                    pad = jnp.full((caps[what] - old, self.PW), SENTINEL,
                                   jnp.int32)
                    frontier = jnp.concatenate([frontier, pad])
                if keep_log and what in ("FCap", "LogCap"):
                    # the log is LogCap rows and one frontier block past
                    # them: either growth lengthens it
                    log = jnp.concatenate([log, jnp.full(
                        (caps[what] - old, self.PW), SENTINEL,
                        jnp.int32)])
                # keep the cap invariants: AccCap >= 2*VC (block-append
                # headroom) and AccCap >= FCap (the frontier is filled
                # from the accumulator's rows) — by x4 steps of AccCap's
                # OWN ladder, so that what a cold run leaves follows from
                # the model's levels alone and not from which overflow
                # came first (a bare max() put AccCap on FCap's ladder:
                # the 4-process rung then ended at AccCap 2^24 where 2^23
                # holds it, after 9 programs where 7 do: PERF.md §6,
                # PR 30)
                while caps["AccCap"] < max(2 * caps["VC"], caps["FCap"]):
                    caps["AccCap"] *= 4
                self.log(f"-- resident: growing {what} to {caps[what]} "
                         f"(level {depth} redone)")
            elif stat == ST_CONTINUE:
                now = time.time()
                if now - last_progress >= self.progress_every:
                    last_progress = now
                    self.log(f"Progress({depth}): {generated} states "
                             f"generated, {distinct} distinct states "
                             f"found, {fcount} states left on queue."
                             f"{obs.eta_suffix(distinct)}")
                if self.checkpoint_path and \
                        now - last_ck >= self.checkpoint_every:
                    last_ck = now
                    self._write_ck(
                        "resident", caps=dict(caps),
                        seen=np.asarray(seen[:seen_count]),
                        frontier=np.asarray(frontier[:fcount]),
                        distinct=distinct, generated=generated,
                        depth=depth)
            elif stat == ST_DONE:
                with tel.span("search.finish"):
                    # remember enough levels-per-dispatch to cover the whole
                    # search in ONE dispatch on a warm re-run (tiny models:
                    # per-dispatch overhead dominated the r04 inversion)
                    self._res_maxlvl_warm = min(
                        max(depth + 1, maxlvl), self._res_maxlvl)
                    self.log("Model checking completed. No error has been "
                             "found.")
                    self.log(f"{generated} states generated, {distinct} "
                             f"distinct states found, 0 states left on queue.")
                    self.log(f"The depth of the complete state graph search "
                             f"is {depth}.")
                    if self._tiers is not None and self._tiers.active:
                        # tier sizes are LEARNED per (module, layout_sig,
                        # platform) like SC/FCap: persist the cold-tier
                        # key total so the next run on this engine knows
                        # the out-of-core magnitude up front
                        self._save_caps_profile(
                            dict(caps, TIERK=_pow2_at_least(
                                max(len(self._tiers), 1), lo=256)),
                            optional=("TIERK", "LogCap"))
                    else:
                        self._save_caps_profile(caps,
                                                optional=("LogCap",))
                    if self.checkpoint_path and self.final_checkpoint:
                        # COMPLETED-run checkpoint (serve warm resume): an
                        # empty frontier over the full seen set — resuming
                        # it replays the stored totals in one dispatch
                        self._write_ck(
                            "resident", caps=dict(caps),
                            seen=np.asarray(seen[:seen_count]),
                            frontier=np.zeros((0, self.PW), np.int32),
                            distinct=distinct, generated=generated,
                            depth=depth)
                    return self._mk_result(True, distinct, generated,
                                           depth - 1, t0, warnings)
            elif stat == ST_TRUNC:
                self.log("-- state limit reached, search truncated")
                self._save_caps_profile(caps, optional=("LogCap",))
                if self.checkpoint_path:
                    # a truncated resident run is RESUMABLE (ISSUE 5):
                    # truncation lands on a level boundary inside the
                    # device loop, so this is exactly the periodic-
                    # checkpoint state — the warm-start bench resumes it
                    # for a steady-state window, and a resumed run's
                    # final counts are bit-identical to an unbounded
                    # cold run (tests/test_warm_bench.py pins it)
                    self._write_ck(
                        "resident", caps=dict(caps),
                        seen=np.asarray(seen[:seen_count]),
                        frontier=np.asarray(frontier[:fcount]),
                        distinct=distinct, generated=generated,
                        depth=depth)
                return self._mk_result(
                    True, distinct, generated, depth, t0, warnings,
                    None, truncated=True,
                    trunc_reason=f"max_states: distinct {distinct} >= "
                                 f"limit {self.max_states}")
            elif stat == ST_OVF_LANES:
                if ovcode == OV_DEMOTED:
                    msg = ("a demoted compile-recovery fired (the "
                           "kernel under-approximates here): run the "
                           "host_seen mode, which demotes the arm to "
                           "the interpreter and restarts — raising "
                           "caps cannot help")
                elif ovcode == OV_PACK:
                    msg = self._pack_ovf_msg()
                else:
                    msg = ("a container exceeded its lane capacity "
                           f"({self._caps_note()})")
                return self._mk_result(
                    False, distinct, generated, depth, t0, warnings,
                    Violation("error", "capacity overflow", [], msg))
            else:
                # a violating search learned its capacities too: the
                # rerun after the fix compiles once
                self._res_maxlvl_warm = min(max(depth + 1, maxlvl),
                                            self._res_maxlvl)
                self._save_caps_profile(caps, optional=("LogCap",))
                trace = None
                if keep_log:
                    trace = self._walk_trace(log, lvl_off, depth, brow,
                                             caps["FCap"], CH)
                    if trace is None:
                        no_trace_why = "a failed walk"
                        warnings.append(
                            f"resident mode: no counterexample trace: "
                            f"the walk over the state log found no "
                            f"parent of its target at some level below "
                            f"{depth} (a fault of the engine: please "
                            f"report it; the level engine gives the "
                            f"trace)")
                if trace is None:
                    trace = [(layout.decode_packed(np.asarray(brow)),
                              f"state reached by resident-mode search "
                              f"(no trace: {no_trace_why})")]
                if stat == ST_INV:
                    nm = self.inv_fns[which][0] if 0 <= which < \
                        len(self.inv_fns) else "invariant"
                    v = Violation("invariant", nm, trace)
                elif stat == ST_DEADLOCK:
                    v = Violation("deadlock", "deadlock", trace)
                else:
                    v = Violation("assert", "Assert", trace,
                                  "assertion failed in an enabled action")
                return self._mk_result(False, distinct, generated, depth,
                                       t0, warnings, v)
            state = (seen, jnp.int32(seen_count), frontier,
                     jnp.int32(fcount), jnp.int32(distinct),
                     jnp.int32(summary[4]), jnp.int32(summary[5]),
                     jnp.int32(depth))

    def _run_host_seen(self) -> CheckResult:
        from .. import native_store
        t0 = time.time()
        tel = obs.current()
        model = self.model
        layout = self.layout
        warnings = ["seen-set resident in the native host fingerprint "
                    "store (host_seen); dedup on 128-bit fingerprints"]
        warnings.extend(self._temporal_warnings())
        warnings.extend(self._symmetry_warnings())
        warnings.extend(self._por_warnings())
        # device POR (ISSUE 18): the ample check probes the native store
        # BEFORE insert via contains(); the store grows chunk-by-chunk, so
        # this engine's probe is (soundly) MORE conservative than the
        # pre-level snapshot the level/resident engines use — a state
        # found by an earlier chunk of the same level counts as seen here
        por_plan = self._por_plan() if self.por else None
        if self.seen_cap is not None:
            # the native store is already host-resident (its growth IS
            # the host tier): name the dropped option instead of
            # silently ignoring it (ISSUE 12)
            self.log("-- host_seen: --seen-cap/JAXMC_SEEN_CAP is "
                     "ignored here (the native fingerprint store is "
                     "host-resident; tier spill applies to the "
                     "device-table modes)")

        init_rows, explored_init, n_init, err = \
            self._prepare_init(t0, warnings)
        if err is not None:
            return err
        generated = self._init_generated
        distinct = len(explored_init)

        store = native_store.FingerprintStore()
        init_keys, init_packed, init_povf = self._host_keys(init_rows)
        if init_povf:
            return self._mk_result(
                False, distinct, generated, 0, t0, warnings,
                Violation("error", "capacity overflow", [],
                          self._pack_ovf_msg()))
        store.insert(init_keys[:, 1:])  # drop the validity lane

        # the frontier lives host-side as a dense PACKED row matrix; each
        # level is processed in fixed-size chunks so the [A, chunk, W]
        # expand tensor is memory-bounded and the jit compiles ONE shape
        CH = _pow2_at_least(self.chunk, lo=64)
        frontier_np = np.ascontiguousarray(init_packed[explored_init])

        graph = _LiveGraph(self.labels_flat, self.collect_edges) \
            if self.live_obligations else None
        frontier_sids = graph.add_inits(init_packed, explored_init) \
            if graph is not None else None

        trace_levels = [(np.asarray(init_packed), None, 0)]
        frontier_maps = [np.asarray(explored_init, dtype=np.int64)]
        depth = 0
        if self.resume_from:
            ck = self._load_ck("host_seen")
            (distinct, generated, depth, tl, fm, graph,
             fsids) = self._restore_ck_state(ck, graph)
            if self.store_trace:
                trace_levels, frontier_maps = tl, fm
            if graph is not None:
                frontier_sids = fsids
            store.load(ck["store"])
            frontier_np = np.ascontiguousarray(ck["frontier"])
        # first progress line immediately (ISSUE 2), in this engine's own
        # interval-line format (see the loop's progress_every site)
        self.log(f"Progress({depth}): {generated} generated, "
                 f"{distinct} distinct, {len(frontier_np)} on "
                 f"queue.{obs.eta_suffix(distinct)}")
        last_progress = last_ck = time.time()
        # cross-model batching hook (ISSUE 13): a batch member's device
        # call routes through the shared vmapped dispatcher instead of
        # its own jit — same signature, same outputs, one dispatch for
        # the whole cohort
        hstep = self._hstep_override(CH) \
            if self._hstep_override is not None else self._get_hstep(CH)
        # the host's side of a level on `time.perf_counter`, published
        # as counters `hostseen.*` at the end of every level (a level
        # that ends the search early keeps its own to itself)
        hs = dict.fromkeys(("step_s", "store_s", "loop_s"), 0.0)
        hs.update(store_keys=0, chunks=0)

        def _hs_flush(tail_s):
            tel.counter("hostseen.chunks", hs["chunks"])
            tel.counter("hostseen.step_s", hs["step_s"])
            tel.counter("hostseen.store_s", hs["store_s"])
            tel.counter("hostseen.store_keys", hs["store_keys"])
            tel.counter("hostseen.book_s",
                        hs["loop_s"] - hs["step_s"] - hs["store_s"])
            tel.counter("hostseen.tail_s", tail_s)
            hs.update(step_s=0.0, store_s=0.0, loop_s=0.0,
                      store_keys=0, chunks=0)

        while len(frontier_np) > 0:
            # chaos sites: simulated hard crash / terminal device failure
            # entering a level (no-ops unless JAXMC_FAULTS names them)
            from .. import faults
            faults.kill_self("run_kill", level=depth, engine="host_seen")
            faults.inject("device_run_fail", level=depth)
            if self._drain_requested(warnings, "host_seen"):
                if self.checkpoint_path:
                    self._write_ck(
                        "host_seen", store=store.dump(),
                        frontier=frontier_np,
                        **self._ck_state_kwargs(distinct, generated,
                                                depth, trace_levels,
                                                frontier_maps, graph,
                                                frontier_sids))
                    self._write_host_snapshot(trace_levels, frontier_maps,
                                              graph, depth, generated)
                return self._mk_result(True, distinct, generated, depth,
                                       t0, warnings, None,
                                       truncated=True, drained=True)
            L = len(frontier_np)
            lvl_t0 = time.time()
            lvl_gen0 = generated
            lvl_new_rows: List[np.ndarray] = []
            lvl_new_prov: List[np.ndarray] = []
            lvl_explore: List[np.ndarray] = []
            lvl_edges: List[Tuple[np.ndarray, np.ndarray]] = []
            lvl_dead = np.zeros(L, bool)  # deferred when fb arms exist
            inv_hit = None

            # SURVEY §2.3 pipeline overlap: chunk i+1 is DISPATCHED to
            # the device before chunk i's outputs are forced, so
            # successor generation overlaps the host-side spill (native
            # store insert), deferred predicate checks, and trace
            # bookkeeping. Exact: the device step depends only on its
            # own chunk, and host processing stays in chunk order.
            # Only when the step actually dispatches asynchronously
            # (the fused jit path — _get_hstep tags it): prefetching a
            # synchronous split step yields no overlap and pays one
            # full wasted chunk on every early exit (OV_DEMOTED
            # restarts included). Cost when active: TWO chunks'
            # [A*CH, W] outputs live at once — size --chunk with that
            # 2x in mind
            prefetch = getattr(hstep, "is_async", False)

            def _dispatch(b, fnp=frontier_np, ll=L):
                c = min(CH, ll - b)
                bf = np.full((CH, self.PW), SENTINEL, np.int32)
                bf[:c] = fnp[b:b + c]
                ts = time.perf_counter()
                out = hstep(bf, c)
                hs["step_s"] += time.perf_counter() - ts
                hs["chunks"] += 1
                return b, c, bf, out

            nxt = None  # one-slot prefetch: the chunk dispatched early
            loop_t0 = time.perf_counter()
            for base in range(0, L, CH):
                _b, cn, buf, out = nxt if nxt is not None \
                    else _dispatch(base)
                nxt = _dispatch(base + CH) \
                    if prefetch and base + CH < L else None
                ovc = int(out["overflow"])
                if ovc:
                    self._last_ovf_code = ovc
                    self._last_frontier_np = frontier_np
                    if ovc == OV_DEMOTED:
                        msg = ("a demoted compile-recovery fired (the "
                               "kernel under-approximates here); the "
                               "hybrid engine demotes the arm and "
                               "restarts")
                    elif ovc == OV_PACK:
                        msg = self._pack_ovf_msg()
                    else:
                        msg = ("a container exceeded its lane capacity "
                               f"({self._caps_note()})")
                    return self._mk_result(
                        False, distinct, generated, depth, t0, warnings,
                        Violation("error", "capacity overflow", [], msg))
                if _any_fast(out["assert_bad"]):
                    ab = np.asarray(out["assert_bad"])
                    ai, f = np.unravel_index(np.argmax(ab), ab.shape)
                    trace = self._trace_to(trace_levels, frontier_maps,
                                           depth, base + int(f))
                    return self._mk_result(
                        False, distinct, generated, depth, t0, warnings,
                        Violation("assert", "Assert",
                                  [x for x in trace if x[0] is not None],
                                  f"assertion in "
                                  f"{self.labels_flat[int(ai)]}"))
                if model.check_deadlock and _any_fast(out["dead"]):
                    if self.fb_arms:
                        # a device-dead state may still have fallback-arm
                        # successors: defer the verdict to after the
                        # interpreter expansion of this level
                        lvl_dead[base:base + cn] = \
                            np.asarray(out["dead"])[:cn]
                    else:
                        f = int(np.argmax(np.asarray(out["dead"])))
                        trace = self._trace_to(trace_levels,
                                               frontier_maps,
                                               depth, base + f)
                        return self._mk_result(
                            False, distinct, generated, depth, t0,
                            warnings,
                            Violation("deadlock", "deadlock", trace))

                cvalid = np.asarray(out["cvalid"])
                keys = np.asarray(out["keys"])
                if por_plan is not None:
                    vidx = np.nonzero(cvalid)[0]
                    found = np.zeros(len(cvalid), dtype=bool)
                    if len(vidx):
                        ts = time.perf_counter()
                        found[vidx] = store.contains(keys[vidx][:, 1:])
                        hs["store_s"] += time.perf_counter() - ts
                    keep, n_amp, n_exp = _por_mask_np(
                        found, cvalid, por_plan["inst_arm"],
                        por_plan["arm_safe"], self.A, CH)
                    self._por_stats["ample"] += int(n_amp)
                    self._por_stats["expanded"] += int(n_exp)
                    self._por_stats["masked"] += \
                        int(np.sum(cvalid & ~keep))
                    cvalid = keep
                    generated += int(np.sum(keep))
                else:
                    generated += int(out["gen"])
                deferred = out.get("deferred_preds", False)
                explore = np.asarray(out["explore"]) \
                    if "explore" in out else None
                if self.refiners:
                    # need_edges implies explore is present in both modes
                    rviol = self._refine_edges(buf, out["cand"], cvalid,
                                               explore, CH)
                    if rviol is not None:
                        a, f, sst, rc = rviol
                        trace = self._trace_to(trace_levels,
                                               frontier_maps,
                                               depth, base + f)
                        return self._mk_result(
                            False, distinct, generated, depth, t0,
                            warnings,
                            self._refine_violation(rc, sst, a, trace))
                if graph is not None and graph.collect_edges:
                    # keep only the masked kept-candidate rows (the full
                    # [A*CH, W] tensor per chunk would hold the whole
                    # level expansion in host RAM)
                    eidx = np.nonzero(cvalid & explore)[0]
                    erows = _take_rows_fast(out["cand"], eidx) \
                        if len(eidx) \
                        else np.zeros((0, self.PW), np.int32)
                    lvl_edges.append((erows, base + eidx % CH))
                valid_idx = np.nonzero(cvalid)[0]
                ts = time.perf_counter()
                new_mask = store.insert(keys[valid_idx][:, 1:])
                hs["store_s"] += time.perf_counter() - ts
                hs["store_keys"] += len(valid_idx)
                new_idx = valid_idx[new_mask]
                if not len(new_idx):
                    continue
                rows_np = _take_rows_fast(out["cand"], new_idx)
                # predicate checks run on NEW rows only (TLC checks each
                # state once): the split hstep defers them entirely —
                # evaluating MCVoting's quantifier-heavy Inv over every
                # one of the A*CH padded candidates was the r3 sweep's
                # compile timeout
                if deferred:
                    inv_okn, exploren = self._check_new_rows(
                        rows_np, skip_cons=explore is not None)
                    if explore is not None:  # need_edges: cons per cand
                        exploren = explore[new_idx]
                else:
                    inv_okn = np.asarray(out["inv_ok"])[new_idx]
                    exploren = explore[new_idx]
                if self.fb_cons:
                    # hybrid: uncompilable CONSTRAINTs evaluate on the
                    # host over decoded new rows (same discard semantics)
                    for k in range(len(rows_np)):
                        if not exploren[k]:
                            continue
                        cctx = model.ctx(
                            state=layout.decode_packed(rows_np[k]))
                        for cnm, cex, _r in self.fb_cons:
                            if not _bool(eval_expr(cex, cctx),
                                         f"constraint {cnm}"):
                                exploren[k] = False
                                break
                # discarded (constraint-violating) states are in the store
                # (fingerprinted) but never counted distinct, checked, or
                # explored — TLC semantics (testout2:265)
                distinct += int(exploren.sum())
                # global provenance: action a, parent base+f within the
                # level's full frontier of length L (cand index = a*CH + f)
                a_ids = new_idx // CH
                f_ids = new_idx % CH
                prov_global = a_ids * L + (base + f_ids)
                bad_mask = (~inv_okn) & exploren
                if inv_hit is None and bad_mask.any():
                    off = sum(len(r) for r in lvl_new_rows)
                    badpos = int(np.nonzero(bad_mask)[0][0])
                    inv_hit = off + badpos
                lvl_new_rows.append(rows_np)
                lvl_new_prov.append(prov_global.astype(np.int64))
                lvl_explore.append(exploren)
                if inv_hit is not None:
                    # the violation is already in hand: skip the rest of
                    # the level's chunks
                    break

            tail_t0 = time.perf_counter()
            hs["loop_s"] += tail_t0 - loop_t0
            if self.fb_arms and inv_hit is None:
                # hybrid: interpreter-enumerate the fallback arms over
                # this level's frontier and splice the results into the
                # same level streams (rows/prov/explore/edges)
                fb_enabled = np.zeros(L, bool)
                gen_inc, dist_inc, fbv = self._fb_expand_level(
                    frontier_np, L, store, lvl_new_rows, lvl_new_prov,
                    lvl_explore, lvl_edges, fb_enabled,
                    trace_levels, frontier_maps, depth, t0, warnings,
                    distinct, generated)
                if fbv is not None:
                    return fbv
                generated += gen_inc
                distinct += dist_inc
                if model.check_deadlock:
                    dead_final = lvl_dead & ~fb_enabled
                    if dead_final.any():
                        f = int(np.nonzero(dead_final)[0][0])
                        trace = self._trace_to(trace_levels,
                                               frontier_maps, depth, f)
                        return self._mk_result(
                            False, distinct, generated, depth, t0,
                            warnings,
                            Violation("deadlock", "deadlock", trace))

            new_rows_np = np.concatenate(lvl_new_rows) if lvl_new_rows \
                else np.zeros((0, self.PW), np.int32)
            new_prov_np = np.concatenate(lvl_new_prov) if lvl_new_prov \
                else np.zeros(0, np.int64)
            explore_mask = np.concatenate(lvl_explore) if lvl_explore \
                else np.zeros(0, bool)

            if inv_hit is None and self.fb_invs:
                # hybrid: uncompilable INVARIANTs evaluate on the host
                # over this level's kept (explored) new states
                for pos in np.nonzero(explore_mask)[0]:
                    ictx = model.ctx(state=layout.decode_packed(
                        new_rows_np[pos]))
                    bad = False
                    for inm, iex, _r in self.fb_invs:
                        if not _bool(eval_expr(iex, ictx),
                                     f"invariant {inm}"):
                            bad = True
                            break
                    if bad:
                        inv_hit = int(pos)
                        break

            if self.store_trace:
                trace_levels.append((new_rows_np, new_prov_np, L))
            if inv_hit is not None:
                st = layout.decode_packed(new_rows_np[inv_hit])
                ctx = model.ctx(state=st)
                nm = next((n for n, ex in model.invariants
                           if not _bool(eval_expr(ex, ctx), n)),
                          model.invariants[0][0] if model.invariants
                          else "invariant")
                trace = self._trace_to(trace_levels, frontier_maps,
                                       depth + 1, inv_hit,
                                       from_new=True) \
                    if self.store_trace else [(st, "?")]
                return self._mk_result(
                    False, distinct, generated, depth + 1, t0, warnings,
                    Violation("invariant", nm, trace))

            sel = np.nonzero(explore_mask)[0]
            if graph is not None:
                new_sids = graph.add_level(new_rows_np[sel],
                                           new_prov_np[sel], L,
                                           frontier_sids)
                for erows, eparents in lvl_edges:
                    graph.add_edges(erows, eparents, frontier_sids)
                frontier_sids = new_sids
            if self.store_trace:
                frontier_maps.append(sel.astype(np.int64))
            tel.level(depth, frontier=L, generated=generated - lvl_gen0,
                      new=len(sel), distinct=distinct, seen=len(store),
                      wall_s=round(time.time() - lvl_t0, 6))
            self._fp_occupancy = len(store)
            _hs_flush(time.perf_counter() - tail_t0)
            depth += 1
            if self.max_states and distinct >= self.max_states:
                self.log("-- state limit reached, search truncated")
                return self._mk_result(
                    True, distinct, generated, depth, t0, warnings,
                    None, truncated=True,
                    trunc_reason=f"max_states: distinct {distinct} >= "
                                 f"limit {self.max_states}")
            frontier_np = new_rows_np[sel]

            now = time.time()
            if self.checkpoint_path and \
                    now - last_ck >= self.checkpoint_every:
                last_ck = now
                self._write_ck(
                    "host_seen", store=store.dump(), frontier=frontier_np,
                    **self._ck_state_kwargs(distinct, generated, depth,
                                            trace_levels, frontier_maps,
                                            graph, frontier_sids))
                self._write_host_snapshot(trace_levels, frontier_maps,
                                          graph, depth, generated)
            if now - last_progress >= self.progress_every:
                last_progress = now
                self.log(f"Progress({depth}): {generated} generated, "
                         f"{distinct} distinct, {len(frontier_np)} on "
                         f"queue.{obs.eta_suffix(distinct)}")

        if graph is not None:
            viol = self._check_live(graph, warnings)
            if viol is not None:
                return self._mk_result(False, distinct, generated,
                                       depth - 1, t0, warnings, viol)
        self.log("Model checking completed. No error has been found.")
        self.log(f"{generated} states generated, {distinct} distinct "
                 f"states found, 0 states left on queue.")
        if self.checkpoint_path and self.final_checkpoint:
            # COMPLETED-run checkpoint (serve warm resume): an empty
            # frontier over the full store — resuming it skips the
            # level loop and replays the stored totals
            self._write_ck(
                "host_seen", store=store.dump(),
                frontier=np.zeros((0, self.PW), np.int32),
                **self._ck_state_kwargs(distinct, generated, depth,
                                        trace_levels, frontier_maps,
                                        graph, frontier_sids))
        return self._mk_result(True, distinct, generated, depth - 1, t0,
                               warnings)

    def _fb_expand_level(self, frontier_np, L, store, lvl_new_rows,
                         lvl_new_prov, lvl_explore, lvl_edges, fb_enabled,
                         trace_levels, frontier_maps, depth, t0, warnings,
                         distinct, generated):
        """Hybrid execution, action side (VERDICT r3 #2): enumerate the
        fallback arms with the EXACT interpreter over this level's
        decoded frontier states, encode the successors, dedup them
        through the native store, and splice rows/provenance into the
        level streams so traces, refinement, and the liveness behavior
        graph see one uniform level. Fallback arm j uses provenance
        action index A + j (labels_flat is extended accordingly).

        Returns (generated_inc, distinct_inc, violation CheckResult |
        None); mutates lvl_* and fb_enabled in place."""
        model = self.model
        layout = self.layout
        base_ctx = model.ctx()
        gen_inc = 0
        cand_rows: List[np.ndarray] = []
        cand_prov: List[int] = []

        def _mk(viol):
            return self._mk_result(False, distinct, generated + gen_inc,
                                   depth, t0, warnings, viol)

        decoded = [layout.decode_packed(frontier_np[f]) for f in range(L)]
        for j, (arm, _reason) in enumerate(self.fb_arms):
            ctx = base_ctx.with_bound(arm.bound)
            for f in range(L):
                pst = decoded[f]
                try:
                    succs = [s for s, _ in enumerate_next(
                        arm.expr, ctx, model.vars, pst)]
                except TLCAssertFailure as ex:
                    trace = self._trace_to(trace_levels, frontier_maps,
                                           depth, f)
                    return gen_inc, 0, _mk(Violation(
                        "assert", "Assert",
                        [x for x in trace if x[0] is not None],
                        str(ex.out)))
                if succs:
                    fb_enabled[f] = True
                gen_inc += len(succs)
                for sst in succs:
                    # constraint check FIRST: a discarded successor is
                    # never explored, counted, or edge-checked, so it
                    # needs no encoding at all — its value shapes may
                    # legitimately be absent from the sampled layout
                    # (skew_fast's cfg discards abort histories, so no
                    # sampled state holds an abort record). Dropping it
                    # here is count-equivalent to fingerprint-and-
                    # discard: satisfaction is state-determined, so the
                    # state can never reappear in an explored context.
                    if not satisfies_constraints(model, sst):
                        continue
                    try:
                        row = np.asarray(layout.encode(sst), np.int32)
                    except (CompileError, EvalError) as ex:
                        # ANY fallback-encode failure is an OBSERVATION
                        # gap relayout can fix: missing variants get
                        # their union slot, and capacity shortfalls grow
                        # because build_layout2 re-derives caps from the
                        # enriched observations. The failing state rides
                        # along so recovery is deterministic even when
                        # the frontier outgrows the enrichment cap.
                        self._last_ovf_code = OV_DEMOTED
                        self._relayout_hint = True
                        self._last_frontier_np = frontier_np
                        self._relayout_states = [sst]
                        return gen_inc, 0, _mk(Violation(
                            "error", "capacity overflow", [],
                            "a fallback successor exceeded its lane "
                            f"capacity ({ex}; {self._caps_note()}); "
                            "counts would no longer be exact"))
                    # EVERY invariant (compiled and demoted alike)
                    # checks host-side on fallback successors: the
                    # device inv pass only sees device candidates
                    ictx = model.ctx(state=sst)
                    for inm, iex in model.invariants:
                        if not _bool(eval_expr(iex, ictx),
                                     f"invariant {inm}"):
                            trace = self._trace_to(
                                trace_levels, frontier_maps, depth, f)
                            trace = [x for x in trace
                                     if x[0] is not None]
                            trace.append(
                                (sst, self.labels_flat[self.A + j]))
                            return gen_inc, 0, _mk(Violation(
                                "invariant", inm, trace))
                    if self.refiners:
                        for rc in self.refiners:
                            if not rc.check_edge(pst, sst):
                                trace = self._trace_to(
                                    trace_levels, frontier_maps, depth, f)
                                return gen_inc, 0, _mk(
                                    self._refine_violation(
                                        rc, sst, self.A + j, trace))
                    cand_rows.append(row)
                    cand_prov.append((self.A + j) * L + f)

        if not cand_rows:
            return gen_inc, 0, None
        # every row collected above is constraint-satisfying (discarded
        # successors were dropped before encoding — they are never
        # counted, checked, or explored, so the drop is count-equivalent
        # to TLC's fingerprint-and-discard)
        rows_mat = np.stack(cand_rows)
        keys, packed_mat, povf = self._host_keys(rows_mat)
        if povf:
            # a packed-lane overflow on a fallback successor is the same
            # OBSERVATION-GAP class as an encode failure: relayout
            # re-profiles the lane ranges from the enriched samples
            self._last_ovf_code = OV_DEMOTED
            self._relayout_hint = True
            self._last_frontier_np = frontier_np
            self._relayout_states = []
            return gen_inc, 0, _mk(Violation(
                "error", "capacity overflow", [],
                f"a fallback successor escaped its packed lane range "
                f"({self._pack_ovf_msg()})"))
        if self.collect_edges:
            # every explored successor EDGE (revisits included) feeds the
            # behavior graph, mirroring the device candidate stream
            lvl_edges.append(
                (packed_mat, np.asarray([p % L for p in cand_prov])))
        new_mask = store.insert(keys[:, 1:])
        new_idx = np.nonzero(new_mask)[0]
        dist_inc = len(new_idx)
        if len(new_idx):
            lvl_new_rows.append(packed_mat[new_idx])
            lvl_new_prov.append(np.asarray(
                [cand_prov[i] for i in new_idx], np.int64))
            lvl_explore.append(np.ones(len(new_idx), bool))
        return gen_inc, dist_inc, None

    def _relayout_and_restart(self) -> Optional[CheckResult]:
        """Adaptive relayout (hybrid): decode the abort-time frontier,
        interp-enumerate one exact level of its successors, and build a
        FRESH engine whose layout sampling includes those states — the
        value shape that fired the demotion is then observed, its union
        variant exists, and the restarted search stays compiled.
        Returns the fresh engine's result, or None when enrichment
        fails (caller falls back to arm demotion)."""
        model = self.model
        cap = 20000
        rows = self._last_frontier_np
        if len(rows) > cap:
            if self.relayouts_left <= 1 and len(rows) <= 10 * cap:
                # last attempt: pay for the FULL frontier (bounded at
                # 10x the per-attempt cap) — a sample that misses the
                # offending parent row would repeat the same abort and
                # waste the attempt. Frontiers beyond the bound stay
                # strided; arm demotion remains the exact safety valve
                self.log(f"hybrid: final relayout attempt — enriching "
                         f"from ALL {len(rows)} abort-frontier rows")
            else:
                # stride over the WHOLE frontier (not a prefix: the
                # missing variant's parent can sit anywhere), with a
                # per-attempt offset so a repeated abort at the same
                # frontier enriches from DIFFERENT rows each time
                stride = -(-len(rows) // cap)
                off = self.relayouts_left % stride
                self.log(f"hybrid: relayout enrichment strided (rows "
                         f"{off}::{stride} of {len(rows)} in the abort "
                         f"frontier)")
                rows = rows[off::stride]
        # states whose encode failed are known exactly — include them
        # directly so recovery never depends on the cap
        enrich: List[Dict[str, Any]] = list(self._relayout_states)
        base_ctx = model.ctx()
        enrich_cap = 400_000  # hard memory ceiling on successor dicts
        try:
            for row in rows:
                # frontier states themselves are already encodable (they
                # were just decoded from this layout): only their
                # SUCCESSORS can carry unobserved shapes
                st = self.layout.decode_packed(np.asarray(row))
                for succ, _ in enumerate_next(model.next, base_ctx,
                                              model.vars, st):
                    enrich.append(succ)
                if len(enrich) >= enrich_cap:
                    self.log(f"hybrid: relayout enrichment truncated "
                             f"at {len(enrich)} successor states "
                             f"(memory ceiling)")
                    break
        except (EvalError, TLCAssertFailure):
            return None
        self.log(f"hybrid: adaptive relayout — re-sampling with "
                 f"{len(enrich)} abort-frontier states, rebuilding "
                 f"kernels, restarting compiled "
                 f"({self.relayouts_left - 1} attempts left)")
        obs.current().counter("expand.relayouts")
        obs.current().reset_levels("adaptive relayout restart")
        if self.checkpoint_path:
            # a checkpoint written under the enriched layout could not
            # be resumed (the resume path re-derives the layout from
            # plain sampling, so the layout signature would mismatch):
            # disable checkpointing rather than strand the user with an
            # unresumable file. Persisting enrichment states in the
            # checkpoint is the known follow-up (ROADMAP).
            self.log("hybrid: relayout disables checkpointing for the "
                     "restarted run (the enriched layout would make "
                     "checkpoints unresumable)")
        try:
            ex2 = TpuExplorer(
                model, log=self.log, max_states=self.max_states,
                store_trace=self.store_trace,
                progress_every=self.progress_every, bounds=self.bounds,
                sample_cfg=self.sample_cfg, host_seen=True,
                chunk=self.chunk,
                extra_samples=self.extra_samples + enrich,
                relayouts_left=self.relayouts_left - 1)
        except (CompileError, ModeError):
            return None
        return ex2.run()

    def _demote_arms(self, arm_idxs) -> List[str]:
        """Hybrid runtime demotion: move the given arms' compiled
        kernels to the interpreter-fallback list and clear the step
        caches. Called when a demoted guard conjunct's abort flag fires
        (see __init__._demotable); the caller restarts the search."""
        idxset = set(arm_idxs)
        reasons: Dict[int, List[str]] = {ai: [] for ai in idxset}
        labels: List[str] = []
        for i, ca in enumerate(self.compiled):
            ai = self._ca_arm[i]
            if ai in idxset:
                reasons[ai].extend(ca.demoted_guards)
                labels.append(ca.label)
        keep = [(ga, ca, ai) for ga, ca, ai in
                zip(self.actions, self.compiled, self._ca_arm)
                if ai not in idxset]
        self.actions = [g for g, _, _ in keep]
        self.compiled = [c for _, c, _ in keep]
        self._ca_arm = [a for _, _, a in keep]
        self.labels_flat = []
        for ca in self.compiled:
            if ca.n_slots:
                self.labels_flat.extend([ca.label] * ca.n_slots)
            else:
                self.labels_flat.append(ca.label)
        self.A = len(self.labels_flat)
        for ai in sorted(idxset):
            why = "; ".join(dict.fromkeys(reasons[ai])) or \
                "demoted guard conjunct"
            self.fb_arms.append((self.arms[ai], f"guard demoted: {why}"))
        self.labels_flat = self.labels_flat + \
            [arm.label or "Next" for arm, _ in self.fb_arms]
        self.hybrid = True
        self._demotable = []
        # the engine is hybrid now: a cached POR plan would mask arms
        # the interpreter expands out of the device's sight — recompute
        # (the hybrid refusal fires on the restarted run)
        self._por_memo = _POR_UNSET
        self._program_sig_memo = _SIG_UNSET  # hybrid now: unkeyed
        self._step_cache.clear()
        self._hstep_cache.clear()
        # grouped-dispatch plans index the OLD compiled list: stale
        # (jits, inst_blocks) would scatter past the shrunken A
        self._hstep_group_jits.clear()
        self._res_cache.clear()
        self._walk_cache.clear()
        obs.current().counter("expand.recovery_demotions", len(idxset))
        return labels

    # ---- host-side search loop ----
    def run(self) -> CheckResult:
        self._begin_search()
        if self.resident:
            return self._run_resident()
        if self.host_seen:
            self._last_ovf_code = 0
            self._relayout_hint = False
            self._relayout_states: List[Dict[str, Any]] = []
            r = self._run_host_seen()
            while not r.ok and r.violation is not None \
                    and r.violation.kind == "error" \
                    and self._last_ovf_code in (OV_DEMOTED, OV_PACK):
                # a compile-recovery demotion fired (never a true lane
                # overflow — that keeps code OV_CAPACITY). First choice:
                # ADAPTIVE RELAYOUT — when the cause is an OBSERVATION
                # gap (a value shape the sampler missed), re-sampling
                # from the abort frontier and rebuilding the kernels
                # keeps the model fully COMPILED. Structural compiler
                # limitations (extensional-set equality, unbounded
                # CHOOSE, Lambda, unsupported binders) can never be
                # fixed by observation — those demote the arms to the
                # interpreter (exact, slower).
                # OV_PACK (a value escaped its packed lane's profiled
                # range) is ALWAYS an observation gap: the relayout's
                # enriched samples re-profile the lane ranges.
                def _structural(why):
                    return ("extensional" in why or
                            "unbounded CHOOSE" in why or
                            "Lambda" in why or "not supported" in why)
                fixable = (self._last_ovf_code == OV_PACK or
                           self._relayout_hint or any(
                               not _structural(why)
                               for ca in self.compiled
                               for why in ca.demoted_guards))
                if fixable and self.relayouts_left > 0 and \
                        self._last_frontier_np is not None and \
                        len(self._last_frontier_np):
                    r2 = self._relayout_and_restart()
                    if r2 is not None:
                        return r2
                if not self._demotable:
                    break
                demoted = self._demote_arms(self._demotable)
                obs.current().reset_levels("hybrid demotion restart")
                self.log(f"hybrid: demotion abort — falling "
                         f"{demoted} back to the interpreter and "
                         f"restarting")
                self._last_ovf_code = 0
                self._relayout_hint = False
                self._relayout_states = []
                r = self._run_host_seen()
            return r
        t0 = time.time()
        tel = obs.current()
        model = self.model
        W, K = self.W, self.K
        warnings = []
        warnings.extend(self._temporal_warnings())
        warnings.extend(self._symmetry_warnings())
        warnings.extend(self._por_warnings())
        if self.fp_mode:
            warnings.append(
                "wide state (W={}): dedup on 128-bit fingerprints; "
                "collision probability < n^2 * 2^-129".format(W))

        with tel.span("search.init"):
            init_rows, explored_init, n_init, err = \
                self._prepare_init(t0, warnings)
        if err is not None:
            return err
        generated = self._init_generated
        distinct = len(explored_init)

        # seed.keys_s / .tables_s / .upload_s: the host's pieces of the
        # seed on the program's own clock, as in _run_resident
        with tel.span("search.seed"):
            with tel.timed("seed.keys_s"):
                init_keys, init_packed, init_povf = \
                    self._host_keys(init_rows)
            if init_povf:
                return self._mk_result(
                    False, distinct, generated, 0, t0, warnings,
                    Violation("error", "capacity overflow", [],
                              self._pack_ovf_msg()))
            graph = _LiveGraph(self.labels_flat, self.collect_edges) \
                if self.live_obligations else None
            frontier_sids = graph.add_inits(init_packed, explored_init) \
                if graph is not None else None

            FC = _pow2_at_least(max(n_init, 1))
            SC = _pow2_at_least(4 * max(n_init, 1))

            with tel.timed("seed.keys_s"):
                order = np.lexsort(tuple(init_keys[:, i]
                                         for i in reversed(range(K))))
            with tel.timed("seed.tables_s"):
                fr_head = init_packed[explored_init] if n_init \
                    else init_packed
                seen_head = init_keys[order]

            trace_levels: List[Tuple[np.ndarray, Optional[np.ndarray], int]] = []
            trace_levels.append((np.asarray(init_packed), None, 0))
            frontier_maps: List[np.ndarray] = [np.asarray(explored_init,
                                                          dtype=np.int64)]

        depth = 0
        if self.resume_from:
            ck = self._load_ck("level")
            (distinct, generated, depth, tl, fm, graph,
             fsids) = self._restore_ck_state(ck, graph)
            if self.store_trace:
                trace_levels, frontier_maps = tl, fm
            if graph is not None:
                frontier_sids = fsids
            # the checkpoint's rows are the heads
            seen_head, fr_head = ck["seen"], ck["frontier"]
            SC = _pow2_at_least(len(seen_head), SC)
            FC = _pow2_at_least(max(len(fr_head), 1), FC)
        fcount, seen_count = len(fr_head), len(seen_head)

        # both tables made on the device from their heads
        # (`_seed_tables`): no search start builds one on the host
        with tel.span("search.seed"), tel.timed("seed.upload_s"):
            seen, frontier = self._seed_tables(SC, FC, seen_head, fr_head)

        self.log(f"Progress({depth}): {generated} states generated, "
                 f"{distinct} distinct states found, "
                 f"{fcount} states left on queue."
                 f"{obs.eta_suffix(distinct)}")
        last_progress = last_ck = time.time()
        while fcount > 0:
            # chaos sites (see _run_host_seen): crash / device failure
            # entering a level
            from .. import faults
            faults.kill_self("run_kill", level=depth, engine="level")
            faults.inject("device_run_fail", level=depth)
            if self._drain_requested(warnings, "level"):
                if self.checkpoint_path:
                    self._write_ck(
                        "level", seen=np.asarray(seen[:seen_count]),
                        frontier=np.asarray(frontier[:fcount]),
                        **self._ck_state_kwargs(distinct, generated,
                                                depth, trace_levels,
                                                frontier_maps, graph,
                                                frontier_sids))
                    self._write_host_snapshot(trace_levels, frontier_maps,
                                              graph, depth, generated)
                return self._mk_result(True, distinct, generated, depth,
                                       t0, warnings, None,
                                       truncated=True, drained=True)
            lvl_t0 = time.time()
            with tel.span("level.dispatch"):
                C = self.A * FC
                if seen_count + C > SC:
                    SC2 = _pow2_at_least(seen_count + C, SC)
                    if self.seen_cap is not None and SC2 > self.seen_cap:
                        if seen_count > 0:
                            # device tier full (ISSUE 12): compact the
                            # sorted prefix out to the cold tiers and
                            # restart the device table empty, instead of
                            # growing past the cap — kept rows are
                            # cold-probed after each step
                            seen = self._tier_spill(seen, seen_count)
                            seen_count = 0
                            SC2 = _pow2_at_least(C, SC)
                        if SC2 > max(SC, self.seen_cap):
                            # the per-level candidate block alone exceeds
                            # the cap: the rank-merge no-overflow invariant
                            # (seen_count + C <= SC) forces a soft breach
                            self._note_cap_breach(
                                SC2, f"one level's candidate block ({C})")
                    if SC2 > SC:
                        pad = jnp.full((SC2 - SC, K), SENTINEL, jnp.int32)
                        seen = jnp.concatenate([seen, pad])
                        SC = SC2
                step = self._get_step(SC, FC)
                # the tables carried from level to level (the candidate
                # block lives inside the step)
                tel.gauge("merge.build_form", _build_form(self.A * FC))
                tel.gauge("search.table_bytes",
                          4 * (SC * K + FC * self.PW))
                out = step(seen, seen_count, frontier, fcount)

            with tel.span("level.sync"):
                ovc = int(out["overflow"])
                if ovc:
                    if ovc == OV_DEMOTED:
                        msg = ("a demoted compile-recovery fired (the kernel "
                               "under-approximates here): run the host_seen "
                               "mode, which demotes the arm to the "
                               "interpreter and restarts")
                    elif ovc == OV_PACK:
                        msg = self._pack_ovf_msg()
                    else:
                        msg = ("a container exceeded its lane capacity "
                               f"({self._caps_note()}); "
                               "counts would no longer be exact")
                    return self._mk_result(
                        False, distinct, generated, depth, t0, warnings,
                        Violation("error", "capacity overflow", [], msg))
                if bool(jnp.any(out["assert_bad"])):
                    ab = np.asarray(out["assert_bad"])
                    a, f = np.unravel_index(np.argmax(ab), ab.shape)
                    trace = self._trace_to(trace_levels, frontier_maps,
                                           depth, int(f))
                    return self._mk_result(
                        False, distinct, generated, depth, t0, warnings,
                        Violation("assert", "Assert",
                                  [x for x in trace if x[0] is not None],
                                  f"assertion in {self.labels_flat[int(a)]}"))
                if model.check_deadlock and bool(jnp.any(out["dead"])):
                    f = int(jnp.argmax(out["dead"]))
                    trace = self._trace_to(trace_levels, frontier_maps,
                                           depth, f)
                    return self._mk_result(
                        False, distinct, generated, depth, t0, warnings,
                        Violation("deadlock", "deadlock", trace))

                if self.refiners:
                    rviol = self._refine_edges(frontier, out["cand"],
                                               out["cvalid"],
                                               out["explore_all"], FC)
                    if rviol is not None:
                        a, f, sst, rc = rviol
                        trace = self._trace_to(trace_levels, frontier_maps,
                                               depth, f)
                        return self._mk_result(
                            False, distinct, generated, depth, t0, warnings,
                            self._refine_violation(rc, sst, a, trace))

                front_count = int(out["front_count"])
                generated += int(out["gen"])
                if "por_ample" in out:
                    self._por_stats["ample"] += int(out["por_ample"])
                    self._por_stats["expanded"] += int(out["por_expanded"])
                    self._por_stats["masked"] += int(out["por_masked"])
                # cold-tier membership filter (ISSUE 12): rows the device
                # rank-merge called new may duplicate keys spilled to the
                # host/disk tiers — drop them (order-preserving) before
                # they are counted, traced, or explored: exactly the rows
                # the uncapped run's device merge would have dropped, so
                # counts and traces stay bit-identical
                tier_keep = None
                fr_host = fp_host = None
                if self._tiers is not None and self._tiers.active \
                        and front_count:
                    with tel.span("tier.pull", rows=front_count):
                        fkeys = np.asarray(
                            out["front_keys"][:front_count, 1:])
                    dup = self._tier_probe(fkeys)
                    if dup.any():
                        tier_keep = ~dup
                        fr_host = np.ascontiguousarray(np.asarray(
                            out["front_rows"][:front_count])[tier_keep])
                        fp_host = np.ascontiguousarray(np.asarray(
                            out["front_prov"][:front_count])[tier_keep])
                    self._tiers.publish_gauges(int(out["seen_count"]))
                kept_count = len(fr_host) if fr_host is not None \
                    else front_count
                distinct += kept_count  # kept states only (discards excluded)
                seen = out["seen"]
                # what the step put into the table and a CONSTRAINT kept
                # out of the frontier (cold duplicates are no discards)
                discarded = int(out["seen_count"]) - seen_count \
                    - front_count
                seen_count = int(out["seen_count"])
                tel.level(depth, frontier=fcount, generated=int(out["gen"]),
                          new=kept_count, distinct=distinct, seen=seen_count,
                          wall_s=round(time.time() - lvl_t0, 6))
            # work against capacity: the step sorted the whole candidate
            # block and rewrote the whole seen table for gen valid rows
            # ... and binary-searched the query blocks that hold them
            # (every valid candidate is a live query of _rank_merge: the
            # host counts with the kernel's own block rule)
            gen_l = int(out["gen"])
            tel.counter("search.slots_sorted", C)
            tel.counter("search.rows_valid", gen_l)
            tel.counter("search.slots_probed",
                        _probe_blocks(gen_l, C) * _probe_block_rows(C))
            tel.counter("search.seen_slots", SC)
            # ... of which the merge built the blocks that hold a live
            # row after the level (the kernel's own block rule again)
            tel.counter("search.slots_merged",
                        _merge_blocks(seen_count, SC)
                        * _merge_block_rows(SC))
            tel.counter("search.rows_new", kept_count)
            if self.constraint_fns:
                # the predicates ran over every slot of the candidate
                # block (ISSUE 51)
                tel.counter("search.slots_constrained", C)
                tel.counter("search.rows_discarded", discarded)
            self._fp_occupancy = seen_count

            with tel.span("level.rows"):
                if graph is not None:
                    new_sids = graph.add_level(
                        fr_host if fr_host is not None else
                        np.asarray(out["front_rows"][:front_count]),
                        fp_host if fp_host is not None else
                        np.asarray(out["front_prov"][:front_count]),
                        FC, frontier_sids)
                    if graph.collect_edges:
                        # the step emits cand/explore_all iff need_edges —
                        # which collect_edges implies
                        mask = np.asarray(out["cvalid"]) & np.asarray(
                            out["explore_all"])
                        idx = np.nonzero(mask)[0]
                        rows = np.asarray(jnp.take(
                            out["cand"], jnp.asarray(idx, dtype=jnp.int32),
                            axis=0)) if len(idx) \
                            else np.zeros((0, self.PW), np.int32)
                        graph.add_edges(rows, idx % FC, frontier_sids)
                    frontier_sids = new_sids

                if self.store_trace:
                    # trace levels hold the kept states; every kept state is
                    # explored, so the frontier map is the identity
                    if fr_host is not None:
                        trace_levels.append((fr_host, fp_host, FC))
                    else:
                        fr_h = np.asarray(
                            out["front_rows"][:max(front_count, 1)])
                        fp_h = np.asarray(
                            out["front_prov"][:max(front_count, 1)])
                        trace_levels.append(
                            (fr_h[:front_count], fp_h[:front_count], FC))
                    frontier_maps.append(
                        np.arange(kept_count, dtype=np.int64))
            with tel.span("level.sync"):
                if bool(out["inv_bad_any"]):
                    idx = int(out["inv_bad_idx"])
                    if tier_keep is not None:
                        # a tier-duplicate row can never violate (its state
                        # was invariant-checked when first admitted), so
                        # the violating row survives the filter: re-index
                        # it into the filtered level
                        idx = int(np.sum(tier_keep[:idx]))
                    which = int(out["inv_bad_which"])
                    nm = self.inv_fns[which][0]
                    trace = self._trace_to(trace_levels, frontier_maps,
                                           depth + 1, idx, from_new=True)
                    return self._mk_result(
                        False, distinct, generated, depth + 1, t0, warnings,
                        Violation("invariant", nm, trace))
            depth += 1

            if self.max_states and distinct >= self.max_states:
                self.log("-- state limit reached, search truncated")
                return self._mk_result(
                    True, distinct, generated, depth, t0, warnings,
                    None, truncated=True,
                    trunc_reason=f"max_states: distinct {distinct} >= "
                                 f"limit {self.max_states}")

            with tel.span("level.dispatch"):
                if kept_count > FC:
                    FC = _pow2_at_least(kept_count, FC)
                if fr_host is not None:
                    nf_np = np.full((FC, self.PW), SENTINEL, np.int32)
                    nf_np[:kept_count] = fr_host
                    frontier = jnp.asarray(nf_np)
                else:
                    nf = jnp.full((FC, self.PW), SENTINEL, jnp.int32)
                    nf = nf.at[:min(front_count, FC)].set(
                        out["front_rows"][:min(front_count, FC)])
                    frontier = nf
                fcount = kept_count

            now = time.time()
            if self.checkpoint_path and \
                    now - last_ck >= self.checkpoint_every:
                last_ck = now
                self._write_ck(
                    "level", seen=np.asarray(seen[:seen_count]),
                    frontier=np.asarray(frontier[:fcount]),
                    **self._ck_state_kwargs(distinct, generated, depth,
                                            trace_levels, frontier_maps,
                                            graph, frontier_sids))
                self._write_host_snapshot(trace_levels, frontier_maps,
                                          graph, depth, generated)
            if now - last_progress >= self.progress_every:
                last_progress = now
                self.log(f"Progress({depth}): {generated} states generated, "
                         f"{distinct} distinct states found, "
                         f"{fcount} states left on queue."
                         f"{obs.eta_suffix(distinct)}")

        if graph is not None:
            viol = self._check_live(graph, warnings)
            if viol is not None:
                return self._mk_result(False, distinct, generated,
                                       depth - 1, t0, warnings, viol)
        with tel.span("search.finish"):
            self.log("Model checking completed. No error has been found.")
            self.log(f"{generated} states generated, {distinct} distinct states "
                     f"found, 0 states left on queue.")
            self.log(f"The depth of the complete state graph search is "
                     f"{depth}.")
            if self.checkpoint_path and self.final_checkpoint:
                # COMPLETED-run checkpoint (serve warm resume): an empty
                # frontier over the full seen table — resuming it skips the
                # level loop and replays the stored totals
                self._write_ck(
                    "level", seen=np.asarray(seen[:seen_count]),
                    frontier=np.zeros((0, self.PW), np.int32),
                    **self._ck_state_kwargs(distinct, generated, depth,
                                            trace_levels, frontier_maps,
                                            graph, frontier_sids))
            return self._mk_result(True, distinct, generated, depth - 1, t0,
                                   warnings)

    def _mk_result(self, ok, distinct, generated, diameter, t0, warnings,
                   violation=None, truncated=False,
                   drained=False,
                   trunc_reason: Optional[str] = None) -> CheckResult:
        tel = obs.current()
        tel.high_water("device.mem_high_water_bytes",
                       obs.device_mem_high_water())
        occ = getattr(self, "_fp_occupancy", None)
        if occ is not None:
            tel.gauge("fingerprint.occupancy", occ)
        if truncated and self.live_obligations:
            warnings.append("temporal properties NOT checked: the "
                            "search was truncated (behavior graph "
                            "incomplete)")
        # ISSUE 12 result surface: the dedup-key mode, the fingerprint
        # collision-probability bound over every ADMITTED key (device
        # occupancy + cold tiers — discarded states hold keys too), the
        # tier-hierarchy summary, and the named exhausted resource on
        # truncations (a bare `truncated` flag cannot tell a deliberate
        # --max-states from a capacity wall)
        tiers_stats = self._tiers_result(occ)
        # device POR end-of-run counters (ISSUE 18): every engine funnels
        # its result through here, so the gauge surface is uniform
        self._por_finish(self._por_stats["ample"],
                         self._por_stats["expanded"],
                         self._por_stats["masked"], distinct)
        if self.canon_fn is not None:
            # every generated state went through the canonicaliser once:
            # successors on the device, the initial states on the host
            tel.counter("search.canon_rows", generated)
        seen_mode = "fingerprint" if self.fp_mode else "exact"
        collision_p = None
        if self.fp_mode:
            n = float((occ or 0) +
                      (len(self._tiers) if self._tiers is not None
                       else 0))
            collision_p = n * n * 2.0 ** -129
            tel.gauge("fingerprint.collision_p", collision_p)
        if truncated and trunc_reason is None:
            trunc_reason = "drain" if drained else "unattributed"
        if trunc_reason:
            tel.gauge("truncation.reason", trunc_reason)
        return CheckResult(ok=ok, distinct=distinct, generated=generated,
                           diameter=max(diameter, 0), violation=violation,
                           wall_s=time.time() - t0, truncated=truncated,
                           warnings=warnings, drained=drained,
                           trunc_reason=trunc_reason,
                           seen_mode=seen_mode, collision_p=collision_p,
                           tiers=tiers_stats)

    def _tiers_result(self, occ) -> Optional[Dict[str, Any]]:
        """`result.tiers` and the end-of-search tier gauges: the cold
        store's stats where it holds a run, and `cap_breached` (rows the
        table grew to) where the cap was soft-breached — with or
        without a spill."""
        tiers_stats = None
        if self._tiers is not None and self._tiers.active:
            tiers_stats = self._tiers.stats()
        if tiers_stats is not None or self.seen_cap is not None:
            # where a capped search's keys ended, spilled or not: the
            # gauge is sticky, and a search that never spilled must not
            # leave the last one's host and disk counts standing
            self._ensure_tiers().publish_gauges(occ or 0)
        if self._cap_breached:
            tiers_stats = dict(tiers_stats or {},
                               cap_breached=self._cap_breached)
        return tiers_stats

    def _drain_requested(self, warnings, engine: str) -> bool:
        """Cooperative drain poll at a device-safe boundary (between
        dispatches / at a level barrier).  Appends the named warning and
        emits the trace event; the CALLER writes its own mode-specific
        checkpoint and returns a drained result."""
        from .. import drain as _drain
        if not _drain.requested():
            return False
        why = _drain.reason()
        self.log(f"-- drain requested ({why}): stopping at a safe "
                 f"boundary")
        obs.current().event("drain", reason=why, engine=engine)
        warnings.append(
            f"run drained before completion ({why})"
            + (f"; resume with --resume {self.checkpoint_path}"
               if self.checkpoint_path else "; no checkpoint was "
               "configured — progress was discarded"))
        return True

    def _trace_to(self, trace_levels, frontier_maps, level: int, idx: int,
                  from_new: bool = False) -> List[Tuple[Dict, str]]:
        if not self.store_trace:
            return []
        out = []
        lvl = level
        cur = idx
        if not from_new and lvl < len(frontier_maps):
            cur = int(frontier_maps[lvl][cur])
        while lvl >= 0:
            rows, prov, par_FC = trace_levels[lvl]
            row = rows[cur]
            st = self.layout.decode_packed(row)
            if prov is None:
                out.append((st, "Initial predicate"))
                break
            p = int(prov[cur])
            a, f = p // par_FC, p % par_FC
            out.append((st, self.labels_flat[a]))
            lvl -= 1
            cur = int(frontier_maps[lvl][f]) if lvl < len(frontier_maps) \
                else f
        out.reverse()
        return out
