"""Bytes that LEAVE a chip in one search of the sharded engine, from its
shapes alone.

Every level, each of the D shards sends every peer one bucket of B rows and
one spill bucket of SB rows through two `all_to_all`s, full or not: the
buckets are sized by capacity, not by what the level generated, so the
traffic of a level is a constant of the pinned capacities.  A row is the
key (validity lane + `key_words`), the packed state (`state_words`) and the
candidate's source index, as int32 words.

    C  = instances x FC            candidate slots a shard expands a level
    B  = max(1, ceil(C x gamma / D), ceil(FC / D))
    SB = max(1, B // 4)
    a level  = (D - 1) x (B + SB) rows x row bytes     LEAVE one chip
    a search = levels x that

That is what the links carry, padding and all (`exchange_mb_per_search`
reads the program's count of it).  What they carry of USE is the rows that
exist: `routed_bytes_leaving_a_chip`, the numerator of
`exchange_ici_roofline`, so that a wider bucket cannot raise that share.

`jaxmc/backend/mesh.py` (`_a2a_bucket`, `_a2a_spill_bucket`, `_route_fn`)
sizes the buckets so; its own counter `mesh.exchange_bytes` counts the
whole mesh, the chip's own bucket included: D x D x (B + SB) rows a level,
so what leaves one chip is that times (D - 1) / D^2 (bench/tests check both
against hand-worked rows, and the counter against this on XLA:CPU).
"""

from __future__ import annotations

import math

WORD = 4  # int32 words


def buckets(instances: int, fc: int, gamma16: int, devices: int):
    """(B, SB): rows of one peer's bucket and of its spill bucket."""
    c = instances * fc
    b = max(1, math.ceil(c * (gamma16 / 16.0) / devices),
            math.ceil(fc / devices))
    return b, max(1, b // 4)


def row_bytes(state_words: int, key_words: int) -> int:
    """validity lane + key + packed state + source index."""
    return (1 + key_words + state_words + 1) * WORD


def level_bytes_leaving_a_chip(instances: int, fc: int, gamma16: int,
                               devices: int, state_words: int,
                               key_words: int) -> int:
    b, sb = buckets(instances, fc, gamma16, devices)
    return (devices - 1) * (b + sb) * row_bytes(state_words, key_words)


def search_bytes_leaving_a_chip(levels: int, instances: int, fc: int,
                                gamma16: int, devices: int,
                                state_words: int, key_words: int) -> int:
    return levels * level_bytes_leaving_a_chip(
        instances, fc, gamma16, devices, state_words, key_words)


def routed_bytes_leaving_a_chip(generated: int, devices: int,
                                state_words: int, key_words: int) -> float:
    """What a search MUST send over a chip's links: every generated
    candidate goes to the shard that owns its key, and under a uniform
    owner hash (D - 1) / D of them leave the chip that made them, a D-th
    of all candidates from each chip.  Rows that exist, not bucket slots:
    padding a bucket adds nothing here."""
    return generated / devices * (devices - 1) / devices \
        * row_bytes(state_words, key_words)


def routed_of_run(run):
    """`routed_bytes_leaving_a_chip` from the pins' `generated`, the mix's
    word counts and the cell's chips; None on pins that carry no mesh
    capacities (a one-chip cell routes nothing)."""
    pins, mix = run["pins"], run["mix"]
    if "GAM16" not in (pins.get("res_caps") or {}):
        return None
    return routed_bytes_leaving_a_chip(
        pins["generated"], run["cell"]["chips"], mix["state_words"],
        mix["key_words"])


def ici_share(bytes_leaving: float, seconds: float,
              ici_bits_per_s: float) -> float:
    """Per cent of the interconnect's peak: the least time the bytes could
    take to leave the chip over the time the device spent on them."""
    return 100.0 * (bytes_leaving / (ici_bits_per_s / 8.0)) / seconds
