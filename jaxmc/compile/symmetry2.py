r"""Device-side SYMMETRY canonicalization over encoded state rows.

Every cfg SYMMETRY permutation of model values induces an exact
transformation of the fixed-width lane encoding (compile/vspec.py):
enum lanes remap through a value table, function/set lanes permute
position-wise with the domain, and containers with a canonical internal
order (growset, kvtable) are re-sorted after the element remap — so
``decode . transform == apply_perm . decode`` lane-for-lane. The device
canonical representative of a state row is the lexicographic minimum of
the row over the (closed) permutation group; hashing canonical rows in
``TpuExplorer._keys_of`` gives the same orbit partition — and therefore
the same distinct/generated counts — as the interp backend's
``make_canonicalizer`` (engine/explore.py), TLC's symmetry reduction
(SURVEY.md §5 state-space reduction).

Encodings that cannot be permuted exactly (a permuted domain member
missing from a layout universe, heterogeneous per-key function specs
inside one orbit) raise CompileError; TpuExplorer then falls back to the
unreduced search with the existing SYMMETRY warning.

Two forms compute that minimum (`build_canon2`, ISSUE 47).  The UNROLLED
form applies every non-identity element of the closed group to the row
and keeps the least result: general, and a program that grows with the
order of the group, so it refuses groups over JAXMC_SYM_GROUP_LIMIT.  The
SORTED form serves what users write most — `Permutations(S)` over a set
of interchangeable processes that the state only INDEXES by (function
domains, membership lanes): permuting the members then permutes
equal-width chunks of the row, and the least row is the one whose
members stand sorted by their chunks, which a fixed compare-exchange
network over |S| members finds elementwise, whatever |S|! is.  Both give
the same row bit for bit where both apply (tests/test_symmetry_sort.py).
"""

from __future__ import annotations

import math
import os
from typing import Callable, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .vspec import VS, EnumUniverse, SENTINEL_LANE, CompileError

SENTINEL = np.int32(SENTINEL_LANE)


def _hk(k):
    from .vspec import _hk as h
    return h(k)


def _value_table(pd: Dict, uni: EnumUniverse) -> Optional[np.ndarray]:
    """Index remap table over the enum universe for permutation pd, or
    None when pd fixes every universe member (identity on enum lanes)."""
    n = len(uni)
    tab = np.arange(n, dtype=np.int32)
    changed = False
    for i, v in enumerate(uni.values):
        w = pd.get(v, v)
        if w is not v:
            try:
                tab[i] = uni.index(w)
            except CompileError:
                raise CompileError(
                    f"symmetry image {w} not in the layout's enum "
                    f"universe - deepen layout sampling")
            changed = True
    return tab if changed else None


def _lex_sort_rows(m, key_cols: int):
    """Stable lexicographic sort of the rows of m [c, w] by the first
    key_cols columns (LSD: chained single-key stable sorts — multi-key
    comparators explode XLA compile time inside while loops). SENTINEL
    padding rows sort last (SENTINEL is the int32 maximum)."""
    cols = [m[:, j] for j in range(m.shape[1])]
    for c in reversed(range(key_cols)):
        res = lax.sort(tuple([cols[c]] + cols), num_keys=1,
                       is_stable=True)
        cols = list(res[1:])
    return jnp.stack(cols, axis=1)


def _seg_tf(spec: VS, pd: Dict, uni: EnumUniverse,
            tab: Optional[np.ndarray]) -> Optional[Callable]:
    """Transform for one encoded segment (length spec.width) under pd.
    Returns None when the transform is the identity (common: int lanes,
    domains untouched by pd). Raises CompileError when the encoding
    cannot be permuted exactly."""
    k = spec.kind
    if k in ("justempty", "int", "bool"):
        return None
    if k == "enum":
        if tab is None:
            return None
        jt = jnp.asarray(tab)

        def enum_tf(seg):
            v = seg[0]
            out = jnp.where(v == SENTINEL, v,
                            jt[jnp.clip(v, 0, len(tab) - 1)])
            return out[None]
        return enum_tf

    if k == "fcn":
        # new[key] = old[pd^-1(key)]: position i takes the segment of the
        # source key, itself element-transformed
        inv = {_hk(v): kk for kk, v in pd.items()}
        pos = {_hk(kk): i for i, kk in enumerate(spec.dom)}
        offs = np.cumsum([0] + [e.width for e in spec.elems])
        src_idx, sub_tfs, moved = [], [], False
        for i, kk in enumerate(spec.dom):
            src = inv.get(_hk(kk), kk)
            j = pos.get(_hk(src))
            if j is None:
                raise CompileError(
                    f"symmetry moves {src} outside the function domain "
                    f"{spec.dom}")
            if spec.elems[j] != spec.elems[i]:
                raise CompileError(
                    "heterogeneous function-value specs within one "
                    "symmetry orbit")
            src_idx.append(j)
            moved = moved or j != i
            sub_tfs.append(_seg_tf(spec.elems[j], pd, uni, tab))
        if not moved and all(t is None for t in sub_tfs):
            return None

        def fcn_tf(seg):
            parts = []
            for i, j in enumerate(src_idx):
                sub = seg[offs[j]:offs[j + 1]]
                parts.append(sub if sub_tfs[i] is None else sub_tfs[i](sub))
            return jnp.concatenate(parts) if parts else seg
        return fcn_tf

    if k == "set":
        inv = {_hk(v): kk for kk, v in pd.items()}
        pos = {_hk(m): i for i, m in enumerate(spec.dom)}
        src_idx = []
        for i, m in enumerate(spec.dom):
            src = inv.get(_hk(m), m)
            j = pos.get(_hk(src))
            if j is None:
                raise CompileError(
                    f"symmetry moves {src} outside the set universe "
                    f"{spec.dom}")
            src_idx.append(j)
        if src_idx == list(range(len(spec.dom))):
            return None
        gidx = jnp.asarray(np.asarray(src_idx, np.int32))

        def set_tf(seg):
            return jnp.take(seg, gidx)
        return set_tf

    if k == "seq":
        sub = _seg_tf(spec.elem, pd, uni, tab)
        if sub is None:
            return None
        ew = spec.elem.width

        def seq_tf(seg):
            n = seg[0]
            parts = [seg[:1]]
            for j in range(spec.cap):
                s = seg[1 + j * ew:1 + (j + 1) * ew]
                # zero padding beyond the length lane must NOT remap
                parts.append(jnp.where(j < n, sub(s), s))
            return jnp.concatenate(parts)
        return seq_tf

    if k == "growset":
        sub = _seg_tf(spec.elem, pd, uni, tab)
        if sub is None:
            return None  # remap is identity => sorted order unchanged
        ew = spec.elem.width

        def growset_tf(seg):
            n = seg[0]
            parts = []
            for j in range(spec.cap):
                s = seg[1 + j * ew:1 + (j + 1) * ew]
                # SENTINEL padding beyond the count must NOT remap
                parts.append(jnp.where(j < n, sub(s), s))
            m = jnp.reshape(jnp.concatenate(parts), (spec.cap, ew))
            m = _lex_sort_rows(m, ew)
            return jnp.concatenate([seg[:1], m.reshape(-1)])
        return growset_tf

    if k == "pfcn":
        inv = {_hk(v): kk for kk, v in pd.items()}
        pos = {_hk(kk): i for i, kk in enumerate(spec.dom)}
        offs = np.cumsum([0] + [1 + e.width for e in spec.elems])
        src_idx, sub_tfs, moved = [], [], False
        for i, kk in enumerate(spec.dom):
            src = inv.get(_hk(kk), kk)
            j = pos.get(_hk(src))
            if j is None:
                raise CompileError(
                    f"symmetry moves {src} outside the pfcn universe")
            if spec.elems[j] != spec.elems[i]:
                raise CompileError(
                    "heterogeneous pfcn value specs within one symmetry "
                    "orbit")
            src_idx.append(j)
            moved = moved or j != i
            sub_tfs.append(_seg_tf(spec.elems[j], pd, uni, tab))
        if not moved and all(t is None for t in sub_tfs):
            return None

        def pfcn_tf(seg):
            parts = []
            for i, j in enumerate(src_idx):
                blk = seg[offs[j]:offs[j + 1]]
                bit, val = blk[:1], blk[1:]
                if sub_tfs[i] is not None:
                    # absent entries are zero-padded: remap only present
                    val = jnp.where(bit[0] == 1, sub_tfs[i](val), val)
                parts.append(jnp.concatenate([bit, val]))
            return jnp.concatenate(parts)
        return pfcn_tf

    if k == "union":
        var_tfs = []
        any_tf = False
        for vnames, vfields in spec.variants:
            offs = np.cumsum([0] + [f.width for f in vfields])
            subs = [_seg_tf(f, pd, uni, tab) for f in vfields]
            if any(s is not None for s in subs):
                any_tf = True

            def vtf(seg, offs=offs, subs=subs):
                parts = []
                for i, s in enumerate(subs):
                    fld = seg[offs[i]:offs[i + 1]]
                    parts.append(fld if s is None else s(fld))
                parts.append(seg[offs[-1]:])  # zero tail padding
                return jnp.concatenate(parts)
            var_tfs.append(vtf)
        if not any_tf:
            return None

        def union_tf(seg):
            tag, payload = seg[0], seg[1:]
            out = payload
            for t, vtf in enumerate(var_tfs):
                out = jnp.where(tag == t, vtf(payload), out)
            return jnp.concatenate([seg[:1], out])
        return union_tf

    if k == "kvtable":
        ksub = _seg_tf(spec.elem, pd, uni, tab)
        vsub = _seg_tf(spec.val, pd, uni, tab)
        if ksub is None and vsub is None:
            return None
        kw, vw = spec.elem.width, spec.val.width
        rw = kw + vw

        def kv_tf(seg):
            n = seg[0]
            parts = []
            for j in range(spec.cap):
                blk = seg[1 + j * rw:1 + (j + 1) * rw]
                kb, vb = blk[:kw], blk[kw:]
                nk = kb if ksub is None else ksub(kb)
                nv = vb if vsub is None else vsub(vb)
                nb = jnp.concatenate([nk, nv])
                # SENTINEL padding rows must NOT remap
                parts.append(jnp.where(j < n, nb, blk))
            m = jnp.reshape(jnp.concatenate(parts), (spec.cap, rw))
            # encode sorts rows by the key lanes (keys unique, so the
            # stable key-only sort is deterministic)
            m = _lex_sort_rows(m, kw)
            return jnp.concatenate([seg[:1], m.reshape(-1)])
        return kv_tf

    raise AssertionError(k)


class _NotSortable(Exception):
    """The sorted form does not apply to this model and layout; the
    message says why (the unrolled form takes over)."""


def _network(n: int):
    """Compare-exchange pairs (i < j) of Batcher's odd-even merge sort
    over n wires: O(n log^2 n) comparators, fixed whatever the data."""
    pairs = []
    p = 1
    while p < n:
        k = p
        while k >= 1:
            for j in range(k % p, n - k, 2 * k):
                for i in range(min(k, n - j - k)):
                    if (i + j) // (2 * p) == (i + j + k) // (2 * p):
                        pairs.append((i + j, i + j + k))
            k //= 2
        p *= 2
    return pairs


def _may_hold(av, ids) -> bool:
    """Can a value the bounds analyzer abstracts as `av`
    (analyze/bounds.py, "abstract values") hold one of the model values
    `ids` ANYWHERE inside it?  Unknown means yes."""
    if av is None:
        return False                    # an empty container's element
    tag = av[0]
    if tag in ("int", "bool"):
        return False
    if tag == "enum":
        return av[1] is None or any(id(v) in ids for v in av[1])
    if tag in ("set", "seq"):
        return _may_hold(av[1], ids)
    if tag == "fun":
        return _may_hold(av[1], ids) or _may_hold(av[2], ids)
    if tag == "rec":
        return any(_may_hold(f, ids) for _, f in av[1])
    return True                         # blob, or a tag not known here


def _value_av(av, key):
    """The abstract value of f[key] for a function or record `av`."""
    if av is not None and av[0] == "fun":
        return av[2]
    if av is not None and av[0] == "rec":
        return dict(av[1]).get(key, ("blob",))
    return ("blob",)


def _member_lanes(model, layout, members) -> list:
    """For each of `members` (model values, one factor of the group), the
    row lanes that belong to it, ascending; raises _NotSortable where the
    members are not mere POSITIONS of the layout.

    A member is a position where it is a key of a `fcn` / `pfcn` lane
    block or an element of a `set` block's universe: permuting the
    members then permutes equal-width chunks of the row.  It is a VALUE
    where an enum lane can hold it (`owner` in specs/symtoy.tla): that is
    read off the bounds analyzer's converged abstract state
    (`model._bounds_report`), whose enum components list every value they
    can hold; no report, or one that cannot say, is taken as "can".

    The order of the members is the order of their positions, which has
    to be the same in every block: within a block the chunks then stand
    member by member, so that sorting the members by their lanes in row
    order minimises the row lexicographically (build_canon2)."""
    rep = getattr(model, "_bounds_report", None)
    env = getattr(rep, "env", None)     # a cohort's merged report has none
    if env is None or not rep.converged:
        raise _NotSortable("no converged bounds report says which lanes "
                           "can hold a member")
    ids = {id(m) for m in members}
    lanes = {id(m): [] for m in members}
    order = []

    def clean(spec, av, why):
        """No member below here, as a position or as a value."""
        if _may_hold(av, ids):
            raise _NotSortable(f"{why} can hold a member as a value")
        stack = [spec]
        while stack:
            sp = stack.pop()
            if any(id(k) in ids for k in sp.dom):
                raise _NotSortable(f"{why} is indexed by members again")
            stack.extend(sp.elems)
            stack.extend(x for x in (sp.elem, sp.val) if x is not None)
            stack.extend(f for _, fs in sp.variants for f in fs)

    def positions(spec, why):
        here = [k for k in spec.dom if id(k) in ids]
        if here and len(here) != len(members):
            raise _NotSortable(f"{why} holds some members and not others")
        if here:
            if not order:
                order.extend(here)
            if [id(k) for k in here] != [id(k) for k in order]:
                raise _NotSortable(f"{why} orders the members differently")
        return bool(here)

    def walk(spec, off, av, why):
        k = spec.kind
        if k in ("justempty", "int", "bool"):
            return
        if k == "enum":
            if _may_hold(av, ids):
                raise _NotSortable(f"{why} can hold a member as a value")
        elif k in ("fcn", "pfcn"):
            bit = 1 if k == "pfcn" else 0
            keyed = positions(spec, why)
            chunk = None
            for key, el in zip(spec.dom, spec.elems):
                sub = _value_av(av, key)
                if keyed and id(key) in ids:
                    if chunk is not None and el != chunk:
                        raise _NotSortable(
                            f"{why} has members of different shapes")
                    chunk = el
                    clean(el, sub, f"{why}[{key}]")
                    lanes[id(key)].extend(
                        range(off, off + bit + el.width))
                else:
                    walk(el, off + bit, sub, f"{why}[{key}]")
                off += bit + el.width
        elif k == "set":
            if positions(spec, why):
                for i, key in enumerate(spec.dom):
                    if id(key) in ids:
                        lanes[id(key)].append(off + i)
        else:   # seq, growset, union, kvtable: no member below, at all
            clean(spec, av, why)

    off = 0
    for v in layout.vars:
        walk(layout.specs[v], off, env.get(v, ("blob",)), v)
        off += layout.specs[v].width
    if not order:
        raise _NotSortable("the members index no lane of the layout")
    return [lanes[id(m)] for m in order]


def _sorted_canon(factors) -> Callable:
    """Canonicaliser for a product of full symmetric groups whose members
    are positions: `factors` holds, for each group, its members' lanes
    ([n][k], `_member_lanes`).  Each group's members are sorted by their
    k lanes (lexicographically, signed, as `lex_lt` below compares rows)
    with a fixed compare-exchange network, elementwise over the batch:
    no gather, no table of permutations, a program whose size does not
    know the order of the group."""
    def lex_lt(a, b):
        lt = a[-1] < b[-1]
        for x, y in zip(reversed(a[:-1]), reversed(b[:-1])):
            lt = (x < y) | ((x == y) & lt)
        return lt

    def canon_rows(rows):
        rows = jnp.asarray(rows)
        cols = [rows[:, i] for i in range(rows.shape[1])]
        for lanes in factors:
            sub = [[cols[i] for i in member] for member in lanes]
            for i, j in _network(len(sub)):
                a, b = sub[i], sub[j]
                swap = lex_lt(b, a)
                sub[i] = [jnp.where(swap, y, x) for x, y in zip(a, b)]
                sub[j] = [jnp.where(swap, x, y) for x, y in zip(a, b)]
            for member, vals in zip(lanes, sub):
                for i, v in zip(member, vals):
                    cols[i] = v
        return jnp.stack(cols, axis=1)
    return canon_rows


def _tagged(fn: Callable, form: str, group_order: int) -> Callable:
    """`fn` with what the engines disclose about it: `form` ("sorted" or
    "unrolled": part of `TpuExplorer._program_sig`) and `group_order`
    (elements of the group, the identity included)."""
    fn.form, fn.group_order = form, group_order
    return fn


def build_canon2(model, layout) -> Optional[Callable]:
    """Canonicalizer over encoded rows: fn(rows [N, W]) -> rows, each
    row replaced by the lexicographic minimum of its symmetry orbit.
    None when the model declares no (non-identity) symmetry.  Raises
    CompileError when some lane encoding cannot be permuted.

    Two forms, chosen by the model alone (the result's `.form`):

    "sorted" — the group is a product of full symmetric groups
    (`sem.symmetry.symmetric_factors`: what `Permutations(S)` closes to)
    and the members are mere positions of the layout (`_member_lanes`).
    Permuting members permutes equal chunks of the row, block by block
    in one member order, so the least row of the orbit is the one whose
    members stand sorted by their lanes in row order: a sorting network
    over |S| members (`_sorted_canon`).

    "unrolled" — everything else: one row transform per non-identity
    element of the closed group, and the least of their results."""
    from ..sem.symmetry import symmetric_factors, symmetry_group
    factors = symmetric_factors(model)
    if factors is not None:
        try:
            lanes = [_member_lanes(model, layout, o) for o in factors]
            return _tagged(_sorted_canon(lanes), "sorted", math.prod(
                math.factorial(len(o)) for o in factors))
        except _NotSortable:
            pass
    perms = symmetry_group(model)
    if not perms:
        return None
    # compile-time guard (advisor r2): canon_row unrolls one transform
    # per non-identity group element into EVERY jitted kernel.
    # Permutations of a 5-6 element set closes to 119-719 transforms —
    # an XLA compile explosion. Fall back to the unreduced search (the
    # caller reports the SYMMETRY warning) above the threshold.
    limit = int(os.environ.get("JAXMC_SYM_GROUP_LIMIT", "64"))
    if len(perms) > limit:
        raise CompileError(
            f"symmetry group has {len(perms)} non-identity elements "
            f"(> {limit}): device canonicalization would unroll that "
            f"many transforms into every kernel; falling back to the "
            f"unreduced search (set JAXMC_SYM_GROUP_LIMIT to raise)")

    row_tfs = []
    widths = [layout.specs[v].width for v in layout.vars]
    offs = np.cumsum([0] + widths)
    for pd in perms:
        tab = _value_table(pd, layout.uni)
        seg_tfs = [_seg_tf(layout.specs[v], pd, layout.uni, tab)
                   for v in layout.vars]
        if all(t is None for t in seg_tfs):
            continue  # permutation fixes every lane

        def row_tf(row, seg_tfs=seg_tfs):
            parts = []
            for i, t in enumerate(seg_tfs):
                seg = row[offs[i]:offs[i + 1]]
                parts.append(seg if t is None else t(seg))
            return jnp.concatenate(parts)
        row_tfs.append(row_tf)
    if not row_tfs:
        return None

    def lex_lt(a, b):
        # first differing lane decides; signed int32 order matches the
        # host-side encode ordering
        diff = a != b
        idx = jnp.argmax(diff)
        return jnp.any(diff) & (a[idx] < b[idx])

    def canon_row(row):
        best = row
        for tf in row_tfs:
            cand = tf(row)
            best = jnp.where(lex_lt(cand, best), cand, best)
        return best

    return _tagged(jax.vmap(canon_row), "unrolled", len(perms) + 1)
