r"""cfg SYMMETRY support: the permutation group shared by every backend.

TLC's SYMMETRY statement names a set of model-value permutations
(TLC.tla:13-14 `Permutations`); states are canonicalized to one
representative per orbit. TLC canonicalizes over the GROUP generated by
the declared set — `Permutations(A) \cup Permutations(B)` alone misses
the combined A+B permutations and under-reduces — so the closure under
composition is computed here, once, for both the interp canonicalizer
(engine/explore.py) and the device-row canonicalizer
(compile/symmetry2.py).
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional

from .eval import eval_expr
from .values import Fcn, enumerate_set


def declared_permutations(model) -> List[Dict]:
    """The cfg's SYMMETRY set as written: one ModelValue->ModelValue dict
    per declared permutation (identities included), not closed."""
    if model.symmetry is None:
        return []
    val = eval_expr(model.symmetry, model.ctx())
    return [dict(p.d) for p in enumerate_set(val) if isinstance(p, Fcn)]


def _moved(pd: Dict) -> frozenset:
    return frozenset((id(k), id(v)) for k, v in pd.items() if k is not v)


def symmetric_factors(model) -> Optional[List[List]]:
    """The disjoint sets S1, S2, ... of model values when the group the
    declared permutations generate is Sym(S1) x Sym(S2) x ... — what
    `Permutations(S)`, or a union of such over disjoint sets, closes to —
    else None.  Each set is in first-declared order.

    The generated group moves values only inside the orbits of the
    declared set, so it is a subgroup of the product of the orbits'
    symmetric groups; it is the whole product as soon as the declared set
    holds, for every orbit, the transpositions of a chain through it
    (they generate that orbit's symmetric group).  A declared set that
    generates the product from other generators (a transposition and a
    cycle, say) is not recognised: the callers then close it."""
    base = declared_permutations(model)
    orbit: Dict[int, List] = {}           # id(value) -> its orbit (shared)
    for pd in base:
        for k, v in pd.items():
            a = orbit.setdefault(id(k), [k])
            b = orbit.setdefault(id(v), [v])
            if a is not b:
                a.extend(b)
                for x in b:
                    orbit[id(x)] = a
    factors = [o for o in {id(o): o for o in orbit.values()}.values()
               if len(o) > 1]
    if not factors:
        return None
    declared = {_moved(pd) for pd in base}
    for o in factors:
        for a, b in zip(o, o[1:]):
            if frozenset(((id(a), id(b)), (id(b), id(a)))) not in declared:
                return None
    return factors


def symmetry_group(model) -> List[Dict]:
    """The non-identity elements of the group generated by the cfg's
    SYMMETRY permutation set, as ModelValue->ModelValue dicts. Empty when
    no symmetry is declared or every permutation is the identity."""
    factors = symmetric_factors(model)
    if factors is not None:
        # a product of symmetric groups is written down, not closed:
        # closing Permutations of six values composes half a million
        # pairs of dicts
        group = [{}]
        for o in factors:
            group = [{**g, **dict(zip(o, img))} for g in group
                     for img in itertools.permutations(o)]
        return [pd for pd in group
                if any(k is not v for k, v in pd.items())]
    base = declared_permutations(model)

    def key_of(pd):
        return tuple(sorted((id(k), id(v)) for k, v in pd.items()))

    group = {key_of(pd): pd for pd in base}
    frontier = list(base)
    while frontier:
        nxt = []
        for a in frontier:
            for b in base:
                comp = {k: b.get(a.get(k, k), a.get(k, k))
                        for k in set(a) | set(b)}
                kk = key_of(comp)
                if kk not in group:
                    group[kk] = comp
                    nxt.append(comp)
        frontier = nxt
    return [pd for pd in group.values()
            if any(k is not v for k, v in pd.items())]
