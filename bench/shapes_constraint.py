"""Bytes a CONSTRAINT's judging and keeping must move through HBM, from the
rows it judged (PR 51).

A FLOOR, not a model of the program: every row that entered the seen table
in a level is read once at its PACKED width (to be judged: the predicate
reads the state), and every row the constraint kept is read once and written
once at its packed width (into the next frontier) — and nothing else.
Nothing is counted for the unpack to lanes (a program may judge the packed
words in registers), for the mask, for whatever names the kept rows first (a
sort, a prefix sum), nor for any slot that held no row: the count reads the
ROWS, never a capacity, so it is the same work whatever later implements the
branch, and a share of the roofline read against it can be compared across
implementations.  The resident engine of PR 51 works over every AccCap slot
of a level's buffer (bench/SPANS.constraint.md): what `constraint_fill` says
of the slots, this says of the bytes.
"""

from __future__ import annotations

WORD = 4  # packed rows are int32 words


def constraint_bytes(judged: int, kept: int, state_words: int) -> int:
    """judged rows read once + kept rows read and written once, x the packed
    row x 4 B."""
    return (judged + 2 * kept) * state_words * WORD
