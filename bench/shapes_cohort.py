"""Bytes ONE vmapped dispatch of a cohort must move through HBM, from its
shapes alone: the arguments of `jit(vmap(hstep_core))` read once and its
results written once (`jaxmc/backend/bfs.py` `_hstep_core`, stacked over B
members by `backend/batch.py`).

A FLOOR, as `shapes.py`'s: nothing is counted for the unpacked rows, the
[A, CH, W] successor tensor, the fingerprint's intermediates or any
temporary — those are what the program adds on top, and what
`vstep_hbm_roofline` therefore shows as distance from 100 %.  The padding IS
counted (a dispatch moves the whole [B, CH, PW] block and all A * CH
candidate slots whatever they hold): it is what the program is asked to
move, and `lane_fill` says how much of it was real.
"""

from __future__ import annotations

WORD = 4  # int32 lanes
FLAG = 1  # bool lanes


def vstep_bytes(members: int, chunk: int, state_words: int, arms: int,
                key_lanes: int, lifted: int) -> int:
    """Arguments + results of one dispatch, in bytes.

    arguments  frontier [B, CH, PW] i32, fcount [B] i32, cvecs [B, n] i32
    results    cand [B, C, PW] i32, keys [B, C, key_lanes] i32,
               cvalid / inv_ok / explore [B, C] bool, dead [B, CH] bool,
               assert_bad [B, A, CH] bool, gen / overflow [B] i32
    with C = A * CH candidate slots a member."""
    B, CH, PW, A = members, chunk, state_words, arms
    C = A * CH
    args = B * CH * PW * WORD + B * WORD + B * lifted * WORD
    results = (B * C * PW * WORD + B * C * key_lanes * WORD
               + 3 * B * C * FLAG + B * CH * FLAG + B * A * CH * FLAG
               + 2 * B * WORD)
    return args + results


def vstep_bytes_of(mix: dict):
    """The mix's `vstep` block through `vstep_bytes`; None without one."""
    v = mix.get("vstep")
    if not v:
        return None
    return vstep_bytes(v["members"], v["chunk"], v["state_words"],
                       v["arms"], v["key_lanes"], v["lifted"])
